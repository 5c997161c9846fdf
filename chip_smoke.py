"""Smoke run of the Level-B serving path on one TPU chip.

Serves granite-moe-1b-a400m at its published widths, with random
weights from a seed, through the same ``EnginePool`` / ``ServingEngine``
that ``python -m repro.launch.serve`` drives, in one process:

1. eager phase: one cold and three warm ``generate`` dispatches and one
   ``score``, then the cold start by component group and the device's
   peak memory;
2. reference: the chip's ``score`` logits against the same forward pass
   run in float32 on the host CPU, on the engine's own parameters;
3. profile-guided phase: a second engine cold-started under the policy
   derived from the first engine's report serves ``score``, the entry
   that policy deferred, compiling it on first use.

Every phase prints JSON lines.  The run exits non-zero if JAX finds no
TPU or any phase fails; otherwise the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import enable_compile_cache  # noqa: E402
from repro.serving import EnginePool, LoadPolicy, ServingEngine  # noqa: E402
from repro.serving.engine import score_step  # noqa: E402

ARCH = "granite-moe-1b-a400m"
SEED = 0
PROMPT_LEN = 32
MAX_NEW_TOKENS = 8
WARM_GENERATES = 3
# bound on ||chip - ref|| / ||ref|| over the score logits: the engine
# serves bfloat16, the reference is float32.  On the CPU at the
# published widths, bfloat16 vs float32 gives 0.017-0.051 for 1-16
# layers (seeds 0 and 1), mostly from capacity drops and top-k choices
# that flip under bfloat16; the bound leaves about 2x margin.
REL_ERR_BOUND = 0.1


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def tpu_or_exit():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}, {dev.device_kind!r})")
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), jax=jax.__version__)
    return dev


def hbm() -> dict:
    """Peak device memory so far and the device's limit (None where the
    backend reports no memory stats, as the CPU does)."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_limit")}


def reference_rel_err(eng: ServingEngine, tokens, logits) -> float:
    """||logits - ref|| / ||ref||, ``ref`` being ``score_step`` run in
    float32 on the host CPU over the engine's parameters."""
    cpu = jax.devices("cpu")[0]
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          jax.device_put(eng._params, cpu))
    toks = jax.device_put(np.asarray(tokens, np.int32), cpu)
    ref_cfg = eng.cfg.with_(dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: score_step(ref_cfg, p, t))(params, toks)
    ref = np.asarray(ref, np.float64)
    got = np.asarray(logits, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def smoke(cfg, *, seed: int = SEED) -> None:
    """Run every phase on ``cfg``; raises on the first failure."""
    rng = np.random.default_rng(seed)
    pool = EnginePool({cfg.name: lambda: ServingEngine(
        cfg, seed=seed, prefill_len=PROMPT_LEN)}, max_warm=1)

    def prompt():
        return rng.integers(0, cfg.vocab, (1, PROMPT_LEN))

    # -- eager phase: cold + warm generate, one score
    for i in range(1 + WARM_GENERATES):
        out, lat, path = pool.dispatch(cfg.name, "generate", prompt(),
                                       max_new_tokens=MAX_NEW_TOKENS)
        want = "cold" if i == 0 else "warm"
        if path != want or out.shape != (1, MAX_NEW_TOKENS) or not (
                (out >= 0) & (out < cfg.vocab)).all():
            raise AssertionError(f"generate #{i}: path {path}, "
                                 f"tokens {out!r}")
        emit(phase="eager", entry="generate", path=path, latency_s=lat)
    score_toks = prompt()
    logits, lat, path = pool.dispatch(cfg.name, "score", score_toks)
    if path != "warm" or logits.shape != (1, PROMPT_LEN, cfg.vocab) or \
            not np.isfinite(logits).all():
        raise AssertionError(f"score: path {path}, shape {logits.shape}")
    emit(phase="eager", entry="score", path=path, latency_s=lat)
    eng = pool.warm[cfg.name]
    rep = eng.report()
    emit(phase="eager", cold_start_s=eng.cold_start_s,
         by_group=rep["by_group"], **hbm())

    # -- reference: the chip's score logits vs float32 on the host
    rel = reference_rel_err(eng, score_toks, logits)
    emit(phase="reference", rel_err=rel, bound=REL_ERR_BOUND)
    if not rel < REL_ERR_BOUND:
        raise AssertionError(f"score logits off the float32 reference: "
                             f"rel err {rel} >= {REL_ERR_BOUND}")

    # -- profile-guided phase: a policy from the eager engine's report
    policy = LoadPolicy.from_report(rep)
    if "compile.score" not in policy.lazy_names:
        raise AssertionError(f"policy did not defer score: {policy}")
    guided = ServingEngine(cfg, policy=policy, seed=seed,
                           prefill_len=PROMPT_LEN)
    guided.cold_start()
    comp = guided.registry["compile.score"]
    if comp.ready:
        raise AssertionError("deferred compile.score built at cold start")
    out, lat = guided.serve("score", prompt())
    if not comp.ready or out.shape != (1, PROMPT_LEN, cfg.vocab) or \
            not np.isfinite(out).all():
        raise AssertionError("deferred score entry did not serve")
    emit(phase="guided", cold_start_s=guided.cold_start_s,
         by_group=guided.report()["by_group"],
         lazy=sorted(policy.lazy_names), deferred_entry="score",
         latency_s=lat, deferred_compile_s=comp.init_time, **hbm())


def main() -> None:
    cache_dir = enable_compile_cache()
    dev = tpu_or_exit()
    cache = Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache.update([event]))
    smoke(get_config(ARCH))
    emit(phase="compile_cache", dir=cache_dir,
         hits=cache["/jax/compilation_cache/cache_hits"],
         misses=cache["/jax/compilation_cache/cache_misses"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
