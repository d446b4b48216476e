"""On-chip probe: is engine donation safe under threaded dispatch on TPU?

Round 4 root-caused the rounds-2-4 token-corruption flake to XLA:CPU
async dispatch racing buffer frees under the engines' multi-threaded
callers, with donation the amplifier (tests/conftest.py quarantine note:
async+donation ~2/3 runs dirty on the worst file). The fix gates
donation OFF on the CPU backend (utils.platform.engine_donation) — and
KEEPS it on TPU on the claim that the TPU client has never shown the
race. VERDICT r4 item 6: that claim had no on-chip evidence. This script
is the evidence rig.

Shape mirrors the worst-case producer: a batched serving engine
(donating jits, engine_donation ACTIVE on the TPU backend) decoding N
sessions, while a second thread concurrently dispatches an unrelated
jitted program in a tight loop (the "other threads in the process"
of the engine_donation docstring). Every rep's tokens are compared
against a single-threaded baseline; ANY divergence is a failed probe.

Run (on the machine with the chip):  python scripts/donation_probe_tpu.py
Exit 0 = all reps clean (donation stays on); exit 1 = divergence seen
(flip engine_donation for this backend and record the log).
"""

import sys
import threading
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    get_config,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StageSpec,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
)

REPS = 12
SLOTS = 4
DECODE_STEPS = 24


def serve_once(ex, prompts):
    toks = {}
    for s, prompt in enumerate(prompts):
        h = ex.prefill(f"s{s}", prompt[None, :])
        toks[f"s{s}"] = [int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))]
    for _ in range(DECODE_STEPS):
        out = ex.decode_batch({sid: jnp.asarray([[t[-1]]], jnp.int32)
                               for sid, t in toks.items()})
        for sid in toks:
            toks[sid].append(int(jnp.argmax(out[sid][0, -1])))
    for s in range(SLOTS):
        ex.end_session(f"s{s}")
    return toks


def main() -> int:
    backend = jax.default_backend()
    print(f"backend={backend} devices={jax.devices()}")
    cfg = get_config("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    ex = BatchedStageExecutor(cfg, spec, params, slots=SLOTS, max_len=128,
                              dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
               for _ in range(SLOTS)]

    baseline = serve_once(ex, prompts)   # also warms every compile

    # Contention thread: unrelated donating program dispatched in a tight
    # loop, churning allocations the way co-hosted engines do.
    stop = threading.Event()
    noise_count = [0]

    def noise():
        @jax.jit
        def churn(x):
            return (x @ x) * 1.000001

        x = jax.random.normal(jax.random.PRNGKey(1), (1024, 1024),
                              jnp.bfloat16)
        while not stop.is_set():
            x = churn(x)
            noise_count[0] += 1
            if noise_count[0] % 50 == 0:
                x.block_until_ready()

    th = threading.Thread(target=noise, daemon=True)
    th.start()
    dirty = 0
    try:
        for rep in range(REPS):
            t0 = time.monotonic()
            got = serve_once(ex, prompts)
            ok = got == baseline
            dirty += 0 if ok else 1
            print(f"rep {rep}: {'clean' if ok else 'DIVERGED'} "
                  f"({time.monotonic() - t0:.1f}s, "
                  f"noise dispatches so far {noise_count[0]})")
            if not ok:
                for sid in got:
                    if got[sid] != baseline[sid]:
                        print(f"  {sid}: got {got[sid][:8]}... "
                              f"want {baseline[sid][:8]}...")
    finally:
        stop.set()
        th.join(timeout=5)
    print(f"RESULT backend={backend} reps={REPS} dirty={dirty} "
          f"noise_dispatches={noise_count[0]}")
    return 1 if dirty else 0


if __name__ == "__main__":
    sys.exit(main())
