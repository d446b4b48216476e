"""`tests/engines.py` itself: the engines it hands out share their compiled
programs exactly where a program's trace would be the same, and nowhere
else. (Sixty cases of one file each compiled their own until PR 59.)"""

import jax
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    slot_attention,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)

import engines
from engines import engine, full_spec, greedy_entry, tiny_cfg

IDS = np.asarray([[5, 9, 23, 7, 81]], np.int32)


def tiny(seed=0, make=engine, **kw):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return make(cfg, full_spec(cfg), params,
                **{"slots": 2, "max_len": 32, **kw})


def drive(ex):
    """A prefill, a step and a burst: (first hidden row, the tokens)."""
    h = ex.prefill("s", IDS)
    ex.decode_batch({"s": np.asarray([[3]], np.int32)})
    return np.asarray(h), ex.decode_burst({"s": greedy_entry(4)}, 2)["s"]


def sizes(ex):
    return (ex._prefill_jit._cache_size(), ex._decode_jits[1]._cache_size(),
            ex._burst_jits[2]._cache_size())


def test_a_second_engine_compiles_nothing():
    """Other weights, the same shapes: the second engine runs the first's
    executables (the compile counts stay flat) and gets ITS weights' rows,
    those of an engine that shares nothing."""
    a, b = tiny(0), tiny(1)
    first = drive(a)
    assert sizes(a) == (1, 1, 1)
    assert b._prefill_jit is a._prefill_jit
    assert b._decode_jits is a._decode_jits and b._burst_jits is a._burst_jits
    second = drive(b)
    assert sizes(b) == (1, 1, 1)
    alone = drive(tiny(1, make=batching.BatchedStageExecutor))
    np.testing.assert_array_equal(second[0], alone[0])
    assert second[1] == alone[1] and not np.array_equal(first[0], second[0])


def test_a_new_engine_starts_from_nothing_but_the_programs():
    a = tiny()
    drive(a)
    b = tiny()
    assert b.slot("s") is None and len(b._free) == b.slots
    assert not b.lengths.any() and not np.asarray(b.k).any()
    assert (b.decode_steps, b.burst_dispatches, b.burst_tokens) == (0, 0, 0)
    assert a.slot("s") is not None and a.burst_dispatches == 1


def test_other_shapes_compile_beside_and_other_slots_apart():
    """``max_len`` and the dtype are the arguments' shapes: the same jit,
    one more executable. The slot count is in the closure: other programs."""
    a = tiny()
    drive(a)
    longer = tiny(max_len=64)
    assert longer.programs is a.programs
    drive(longer)
    assert sizes(a) == (2, 2, 2)
    wider = tiny(slots=3)
    assert wider.programs is not a.programs and wider._prefill_jit is None


@pytest.mark.parametrize("patch", [
    lambda m: m.setattr(batching, "ATTN_BLOCK", 8),
    lambda m: m.setattr(batching, "_decode_span",
                        lambda *a: batching._decode_span(*a)),
    lambda m: m.setattr(slot_attention, "_INTERPRET", True),
    lambda m: m.setenv("INT8_FOLD", "0")],
    ids=["a-constant", "a-function", "a-kernel-s-hook", "a-trace-time-flag"])
def test_an_engine_built_under_a_patch_has_programs_of_its_own(
        monkeypatch, patch):
    """What a trace reads of the modules and of the environment is part of
    the key: a program traced without the patch never serves an engine
    built under it, nor the other way; with the patch undone the first
    programs are back."""
    a = tiny()
    with monkeypatch.context() as m:
        patch(m)
        patched = tiny()
        assert patched.programs is not a.programs
        assert tiny().programs is patched.programs        # the same object
    assert tiny().programs is a.programs


def test_a_slot_set_to_none_is_rebuilt_for_everyone():
    """``ex._prefill_jit = None`` is how the engine forgets a program."""
    a, b = tiny(), tiny()
    drive(a)
    b._prefill_jit = None
    assert a._prefill_jit is None
    drive(b)
    assert a._prefill_jit is b._prefill_jit is not None


def test_a_plain_engine_shares_nothing_and_the_module_s_end_forgets():
    a = tiny()
    drive(a)
    plain = tiny(make=batching.BatchedStageExecutor)
    assert plain._prefill_jit is None and not plain._decode_jits
    assert plain._decode_jits is not a._decode_jits
    engines.forget_programs()               # tests/conftest.py, a module
    assert tiny().programs is not a.programs and tiny()._prefill_jit is None
