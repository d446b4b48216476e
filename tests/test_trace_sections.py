"""`scripts/trace_sections.py`: which scope an operation's device time goes
to. The program's own names carry it (`jax.named_scope` on ``tf_op``); a
``conditional`` or a ``while`` the compiler leaves without one takes the
scope of what it holds, when all of that agrees."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sections():
    spec = importlib.util.spec_from_file_location(
        "trace_sections", os.path.join(ROOT, "scripts", "trace_sections.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scope_names_are_found_in_an_op_name(sections):
    find = sections.SCOPE_RE.search
    inside = ("jit(burst_tick)/while/body/closed_call/while/body/"
              "closed_call/attention/cond/branch_3_fun/dot_general")
    assert find(inside).group(1) == "attention"
    assert find("jit(burst_tick)/while/body/attention/while/body/exp"
                ).group(1) == "attention"
    assert find("jit(burst_tick)/while/body/kv_update/scatter"
                ).group(1) == "kv_update"
    assert find("jit(burst_tick)/while/body/attention_like/add") is None


def test_control_flow_takes_the_scope_of_what_it_holds(sections):
    """Events as (metadata id, start, duration), sorted by start and
    longest first. 1: the layer scan's ``while`` (holds every scope: stays
    unscoped). 2: the ``switch`` over block counts, holding two attention
    fusions. 5: a ``while`` over blocks holding an attention fusion and an
    unscoped copy (stays unscoped: not all agree). 8: a conditional of the
    sampler that already has its scope (kept)."""
    att, none = ("attention", "tf_op"), (None, None)
    scope_of = {1: none, 2: none, 3: att, 4: ("mlp", "tf_op"), 5: none,
                6: none, 8: ("sampler", "tf_op"), 9: ("sampler", "tf_op")}
    order = [(1, 0.0, 10.0), (2, 1.0, 2.0), (3, 1.1, 0.5), (3, 2.0, 0.5),
             (4, 4.0, 1.0), (5, 6.0, 2.0), (3, 6.1, 0.5), (6, 7.0, 0.5),
             (8, 11.0, 1.0), (9, 11.2, 0.5)]
    assert sections._control_flow_scopes(order, scope_of) == {
        2: ("attention", "nested operations")}
