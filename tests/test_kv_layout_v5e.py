"""The engine's programs compiled for a DESCRIBED v5e chip (no chip attached:
`jax.experimental.topologies`), at the shapes the benchmark's cells serve:
what the compiler answers when it is asked for the stacks' layout, and that a
program TOLD the answer re-lays no stack. Nothing runs, so this says nothing
of times: PERF.md section 6 (PR 45) has those. The only file of the suite
that loads the TPU's compiler, and only inside its fixtures."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config as config_mod,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    fuse_qkv_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    RIDER_ROWS,
    BatchedStageExecutor,
    layout_text,
)

TICKS = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def engine_of_shapes(monkeypatch, one_chip, preset, slots, max_len,
                     layers=None):
    """A `BatchedStageExecutor` whose weights are shapes on the described
    chip and that has made no stack: enough to ask for the layout and to
    build its programs, as on the chip (donation on, the compiler asked)."""
    import dataclasses

    cfg = config_mod.get_config(preset)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    monkeypatch.setattr(batching, "layout_pin_refused", lambda: None)
    monkeypatch.setattr(batching, "engine_donation", lambda *idx: idx)
    ex = object.__new__(BatchedStageExecutor)
    ex.cfg, ex.spec = cfg, StagePlan.even(cfg.num_layers, 1).stages[0]
    ex.params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if jnp.issubdtype(a.dtype, jnp.floating)
            else a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: fuse_qkv_params(
            init_params(jax.random.PRNGKey(0), cfg))))
    ex.slots, ex.max_len, ex.dtype = slots, max_len, jnp.dtype(jnp.bfloat16)
    ex.lengths = np.zeros((slots,), np.int32)
    ex.rider_rows = RIDER_ROWS if cfg.loop_steps > 1 else 0
    ex.kv_formats = (None, None)
    return ex


def compiled_burst(ex, one_chip):
    shape_of = lambda a: jax.ShapeDtypeStruct(          # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    stacks = [jax.ShapeDtypeStruct(ex._stack_shape(), ex.dtype,
                                   sharding=fmt or one_chip)
              for fmt in ex.kv_formats]
    rider = ([jax.eval_shape(lambda: ex._rider_args(None, TICKS))]
             if ex.rider_rows else [])
    return ex._build_burst(TICKS).lower(
        ex.params, *jax.tree.map(shape_of, list(ex._burst_blank().values())),
        *stacks, *jax.tree.map(shape_of, rider)).compile()


def whole_stack_copies(compiled, shape):
    dims = ",".join(str(d) for d in shape)
    return re.findall(rf"\S*copy\S* = \w+\[{dims}\]\{{[^}}]*\}} copy\(",
                      compiled.as_text())


def test_gpt2_xl_s_burst_re_lays_no_stack_once_it_is_told_the_answer(
        monkeypatch, one_chip):
    """``[48, 8, 1024, 25, 64]``: the device's default is ``max_len`` minor
    (it pads nothing) and a burst compiled for it copies both stacks into
    ``{4,3,2,1,0}`` and back, 6.4 GB of temporaries; asked, the compiler
    answers ``{4,3,2,1,0}`` with (8,128)(2,1) tiles; told that, the burst
    holds no whole-stack copy and the temporaries are the weights' own."""
    ex = engine_of_shapes(monkeypatch, one_chip, "gpt2-xl", 8, 1024)
    bare = compiled_burst(ex, one_chip)
    assert layout_text(bare.input_formats[0][14].layout) == (
        "{2,4,3,1,0:T(8,128)(2,1)}")
    assert len(whole_stack_copies(bare, ex._stack_shape())) == 4
    assert bare.memory_analysis().temp_size_in_bytes > 6.4e9
    ex.kv_formats = ex._ask_kv_formats()
    assert [layout_text(f.layout) for f in ex.kv_formats] == [
        "{4,3,2,1,0:T(8,128)(2,1)}"] * 2
    told = compiled_burst(ex, one_chip)
    assert whole_stack_copies(told, ex._stack_shape()) == []
    for fmt in (*told.input_formats[0][14:16], *told.output_formats[10:12]):
        assert fmt.layout == ex.kv_formats[0].layout
    mem = told.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 0.75 * 16.9e9


@pytest.mark.parametrize("preset, slots, max_len, layers", [
    ("ouro-2.6b", 8, 512, None),    # [192, 8, 512, 16, 128], a rider lane
    ("qwen2-7b", 16, 1024, 8),      # [8, 16, 1024, 4, 128] (bf16 weights)
])
def test_where_the_default_is_the_answer_the_program_is_the_bare_one(
        monkeypatch, one_chip, preset, slots, max_len, layers):
    """Minor dimensions that fill a tile's lanes (``Dh`` 128): the compiler
    answers the layout the device holds the stacks in anyway, and the
    pinned burst is the unpinned one: no whole-stack copy in either, the
    same bytes of arguments and temporaries."""
    ex = engine_of_shapes(monkeypatch, one_chip, preset, slots, max_len,
                          layers)
    bare = compiled_burst(ex, one_chip)
    ex.kv_formats = ex._ask_kv_formats()
    assert [f.layout for f in ex.kv_formats] == [
        f.layout for f in bare.input_formats[0][14:16]]
    told = compiled_burst(ex, one_chip)
    for program in (bare, told):
        assert whole_stack_copies(program, ex._stack_shape()) == []
    was, now = bare.memory_analysis(), told.memory_analysis()
    assert (was.argument_size_in_bytes, was.temp_size_in_bytes) == (
        now.argument_size_in_bytes, now.temp_size_in_bytes)
