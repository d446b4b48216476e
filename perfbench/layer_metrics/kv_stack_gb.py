"""Bytes of the resident K and V cache stacks, in GB (1e9 bytes), summed
over the servers: the gauge's value at the window's end. A gauge is a
level, not a count, so no stock reader (they take deltas) fits. A program
without the gauge gives nothing to read."""


def read(ctx, params):
    levels = [after[params["gauge"]]
              for after in ctx.get("counters_after", {}).values()
              if params["gauge"] in after]
    total = sum(levels)
    return total / 1e9 if total else None
