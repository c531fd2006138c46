#!/usr/bin/env bash
# Tier-1 CI: the full test suite, the planner and autotuner smokes, the
# docs-rot check, and the observability, IR and fuzzer smokes.  Speed is
# measured on the chip by the benchmark BENCHMARK.json declares.
#
#   scripts/ci.sh            # tests + smokes + docs check
#
# The planner smoke plans 7 shapes (one Fig. 5 unfavorable grid, one
# time_steps=3 fused plan, one two-stage heterogeneous chain, one 4-way
# sharded request, one bf16-frontier §14 ring chain) and asserts the pad
# triggers and the planned-traffic + fused<=single-pass +
# streaming<=recompute-flops + per-shard-slab + ring-never-worse gates
# hold.  The autotune smoke (§11) races the planner's top-k candidates on
# the live backend and asserts never_slower, the record round-trip, and
# the sub-ms warm TunedPlanDB hit — plus one §15 chain race whose
# candidate list must span window kinds (ring + trapezoid) AND advisory
# bf16/int8 dtype variants, with the winner never an advisory row and
# the v2 record round-tripping.  check_docs.py fails on documentation
# referencing renamed or removed modules or dangling DESIGN.md § anchors.
# The IR smoke (§13) runs a two-stage heterogeneous chain spelled as a
# program and asserts bit-wise parity with the stages= spelling.
# The obs smoke (§12) runs one tuned 4-way-sharded fused T=3 chain under
# REPRO_TRACE, asserts the trace parses as valid trace_event JSON, and
# gates on repro.obs.report --check reconciling counters against spans
# (including the §14 ring_vmem_bytes counter).  The §15 fuzzer step
# replays the committed differential corpus (random programs vs the
# numpy oracle, tolerance-banded per DESIGN.md §15); when hypothesis is
# installed it widens into fresh generative search.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

# The non-pytest smokes below need the same XLA pins the test suite and
# benchmark harness apply for themselves: a 4-device host platform for
# the §10 mesh launches, and the ISA capped below FMA3 so the §14
# ring↔trapezoid bit-parity holds on CPU.  repro.runtime.isa is the one
# home of the pins (guards, rationale, user-set values win); its
# --export mode prints the eval-able assignment for shell consumers.
eval "$(python -m repro.runtime.isa --export)"

python -m pytest -x -q
python -m repro.plan.explain --smoke
python -m repro.autotune --smoke
python scripts/check_docs.py

# --- §12 observability smoke -------------------------------------------
OBS_TMP="$(mktemp -d)"
trap 'rm -rf "$OBS_TMP"' EXIT
REPRO_TRACE="$OBS_TMP/trace.json" python - <<'PY'
from repro.runtime import isa
isa.pin_xla_flags()
import numpy as np
import jax.numpy as jnp
from repro.core.cache_fitting import star_stencil
from repro.entry import stencil_iterate
from repro.plan import AutoTuner, PlanCache, Planner, TunedPlanDB

offs = star_stencil(3, 1)
w = [1.0 / len(offs)] * len(offs)
u = jnp.asarray(
    np.random.default_rng(0).standard_normal((16, 32, 128)), jnp.float32
)
tuner = AutoTuner(
    db=TunedPlanDB(persistent=False),
    planner=Planner(cache=PlanCache(persistent=False)),
    k=2, reps=2, warmup=1,
)
stencil_iterate(u, offs, w, 3, num_shards=4, tune=tuner)
PY
python - "$OBS_TMP/trace.json" <<'PY'
import json, sys
from repro.obs.trace_event import validate_trace
doc = validate_trace(json.load(open(sys.argv[1])))
counters = doc["otherData"]["counters"]
assert counters["launches"] > 0, counters
assert counters["modeled_bytes"] > 0, counters
print(f"obs smoke: trace valid, {counters['launches']} launches, "
      f"{counters['modeled_bytes']} modeled bytes")
PY
python -m repro.obs.report "$OBS_TMP/trace.json" --check

# --- §13 stencil-program IR smoke --------------------------------------
REPRO_TRACE="$OBS_TMP/ir_trace.json" python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
from repro import ir
from repro.core.cache_fitting import star_stencil
from repro.entry import run, stencil_iterate

offs1 = star_stencil(2, 1)
w1 = list(np.linspace(-0.3, 0.4, len(offs1)))
offs2 = star_stencil(2, 2)
w2 = list(np.linspace(-0.1, 0.12, len(offs2)))
u = jax.random.normal(jax.random.PRNGKey(3), (48, 56), jnp.float32)

staged = stencil_iterate(u, stages=[(offs1, w1), (offs2, w2)],
                         tile=(8, 16), sweep_axis=0)
prog = ir.chain_program([(offs1, w1), (offs2, w2)], d=2)
ir.verify(prog, u.shape)
lowered = run(prog, u, tile=(8, 16), sweep_axis=0)
assert np.array_equal(np.asarray(staged), np.asarray(lowered)), \
    "program spelling diverged from the stages= spelling"
halos = ir.infer_halos(prog)   # keyed by value name; the load is "u0"
print(f"ir smoke: {ir.summarize_program(prog)} bit-wise == stages= "
      f"(input halo {halos['u0']})")
PY
python -m repro.obs.report "$OBS_TMP/ir_trace.json" --check

# --- §15 differential fuzzer, quick profile ----------------------------
# The committed corpus (tests/corpus/) replays deterministically in
# tier-1 already; this names the step so a corpus regression reads as a
# fuzzer failure, not a generic pytest one.  With hypothesis installed
# the same file widens into generative search.
python -m pytest -q tests/test_program_fuzz.py
