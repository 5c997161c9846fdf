"""The benchmark's machinery: find a cell's files by name, pin the
compile cache, check the chip, set the served pool up, drive the open-
loop window through ``EnginePool.dispatch`` and collect what it did.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own that this module finds by the name in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<mix>.json``
(generated by ``traffic/<kind>.py``), ``metrics/<metric>.py`` and
``reference/<family>.py``.

A configuration file states the model with its published keys, as
published, and beside them these keys, which every such file has:

- ``arch`` and ``program_overrides``: the program's configuration
  (``repro.configs.get_config(arch).with_(**program_overrides)``);
- ``engine``: the ``ServingEngine`` keyword arguments of every engine;
- ``check``: the limits and the control of the correctness check
  (``check.py``);
- ``reference``: the model family, ``reference/<family>.py``;
- ``reduced``: the published keys changed to fit the chip;
- ``assumed``: what the file states that the source does not.

The family module reads the published keys (``check.py`` reads
``vocab_size`` alone, a key every family's file has).  It provides
``template``/``weights`` (the served weights rebuilt from a tenant's
seed), ``forward(params, tokens, m, prompt_len, quant=None)`` (float32
logits), ``program_sizes`` (for ``matching``) and ``decode_step`` (for
``metrics/decode_mfu.py``); see ``reference/__init__.py``.  So a model
of another family is new files only.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed, inside the checkout: the path is part of what a later run finds
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(SystemExit):
    """No accelerator of the kind and count the cell needs."""


@dataclass
class Cell:
    name: str
    chips: int
    spec: dict       # configs/<config>.json: the configuration as run
    mix: dict        # traffic/<mix>.json
    bench: dict      # BENCHMARK.json

    def metrics(self, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    spec = json.loads((ROOT / cfg["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["chips"], spec, mix, bench)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_module(mix: dict):
    return load_module(BENCH / "traffic" / f"{mix['kind']}.py")


def metric_module(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def reference_module(spec: dict):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return importlib.import_module(f"reference.{spec['reference']}")


def subseed(seed: int, *tags: int) -> int:
    """A seed below 2**31 derived from the run's seed and ``tags``."""
    return int(np.random.SeedSequence([seed, *tags]).generate_state(1)[0]
               >> 1)


# ------------------------------------------------------ cache and chip
def pin_compile_cache() -> str:
    """JAX's persistent compilation cache at ``CACHE_DIR`` whatever the
    environment says, every program cached however fast it compiled;
    the TPU runtime's own log files off, unless asked for (they would
    go to a fixed directory outside the checkout).  Call before
    importing jax."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CacheEvents:
    """Persistent-cache hits and misses (a miss is a compile), counted
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}


def chip_or_exit(chips: int, peaks: dict):
    """The first device, if it is a TPU listed in ``peaks`` and there
    are ``chips`` of them; otherwise exit non-zero."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"bench: no TPU (JAX's first device is {d.platform!r}"
                     f", {d.device_kind!r})")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    if d.device_kind not in peaks:
        raise NoChip(f"bench: no peaks for device kind {d.device_kind!r} "
                     f"in peaks.json")
    return d


# ------------------------------------------------------------- program
def load_peaks() -> dict:
    """Published peaks by ``device_kind`` (``peaks.json``)."""
    return json.loads((BENCH / "peaks.json").read_text())


def program_config(spec: dict):
    """The program's configuration for ``spec``."""
    from repro.configs import get_config
    return matching(
        get_config(spec["arch"]).with_(**spec["program_overrides"]), spec)


class _Absent:
    """What an attribute path the program's configuration lacks reads."""

    def __repr__(self):
        return "absent"


ABSENT = _Absent()


def attribute(obj, path: str):
    """``obj``'s attribute at the dotted ``path``; ``ABSENT`` where a
    step of it is missing (as any under ``moe`` without experts)."""
    for name in path.split("."):
        obj = getattr(obj, name, ABSENT)
    return obj


def matching(cfg, spec: dict):
    """``cfg``, if its sizes are those the configuration file states:
    every attribute path the family module's ``program_sizes(spec)``
    names holds the value it gives."""
    want = reference_module(spec).program_sizes(spec)
    have = {path: attribute(cfg, path) for path in want}
    if have != want:
        raise SystemExit(f"bench: the program's {spec['arch']} is not the "
                         f"configuration in the file: {have} != {want}")
    return cfg


def drop_engine(eng) -> None:
    for comp in eng.registry.values():
        comp.drop()
    eng._params = None


@dataclass
class Served:
    pool: Any
    tenants: dict            # tenant name -> weight seed
    policy: Any
    first_coldstart_s: float


def set_up(cell: Cell, cfg, seed: int) -> Served:
    """Profile pass, then the pool the window drives.

    An eager engine serves a sample of the cell's own mix (drawn under
    a seed of its own); ``LoadPolicy.from_report`` of its report is the
    policy of every engine the pool cold-starts.  The eager engine is
    dropped before the pool starts."""
    from repro.serving import LoadPolicy, ServingEngine
    prof = ServingEngine(cfg, seed=subseed(seed, 100), **cell.spec["engine"])
    first_cold = prof.cold_start()
    for r in traffic_module(cell.mix).profile_sample(
            cell.mix, subseed(seed, 101), cfg.vocab):
        prof.serve(r["entry"], r["prompt"][None],
                   max_new_tokens=r["new_tokens"])
    policy = LoadPolicy.from_report(prof.report())
    drop_engine(prof)
    del prof
    gc.collect()
    pool, tenants = make_pool(cell, cfg, policy, seed)
    return Served(pool, tenants, policy, first_cold)


def make_pool(cell: Cell, cfg, policy, seed: int):
    """``EnginePool(max_warm=1)`` over the mix's tenants, each engine
    under ``policy`` with weights from its own seed.  With the mix's
    ``resident``, tenant 0 is cold-started through the pool and serves
    each entry once, so the window finds it warm with every entry
    compiled.  Returns (pool, tenant name -> weight seed)."""
    from repro.serving import EnginePool, ServingEngine
    tenants = {f"tenant{t}": subseed(seed, 200, t)
               for t in range(cell.mix["tenants"])}
    pool = EnginePool({name: partial(ServingEngine, cfg, policy=policy,
                                     seed=s, **cell.spec["engine"])
                       for name, s in tenants.items()}, max_warm=1)
    if cell.mix.get("resident"):
        r = traffic_module(cell.mix).profile_sample(
            cell.mix, subseed(seed, 102), cfg.vocab)[0]
        pool.dispatch("tenant0", "generate", r["prompt"][None],
                      max_new_tokens=r["new_tokens"])
        pool.dispatch("tenant0", "score", r["prompt"][None])
    return pool, tenants


def free(served: Served) -> None:
    """Drop every engine the pool holds, so the reference has the chip."""
    for eng in served.pool.warm.values():
        drop_engine(eng)
    served.pool.warm.clear()
    gc.collect()


# -------------------------------------------------------------- window
@dataclass
class Window:
    records: list = field(default_factory=list)
    seconds: float = 0.0          # open to the last response
    traced_s: Optional[float] = None  # the traced part, from the open


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(served: Served, schedule: list, *, trace_seconds: float = 0.0,
          trace_dir: Path = TRACE_DIR) -> Window:
    """Serve ``schedule`` open loop on the host clock, one request at a
    time, in order: a request due while another is served waits, and
    its latency counts from when it was due.  With ``trace_seconds``
    the profiler traces the window's first seconds; the schedule is
    paused while the trace is written out."""
    import jax
    pool = served.pool
    win = Window()
    tracing = trace_seconds > 0
    if tracing:
        jax.profiler.start_trace(str(trace_dir))
        mark = _annotate("bench.traced")
        mark.__enter__()
    t_open = time.perf_counter()
    for i, r in enumerate(schedule):
        now = time.perf_counter()
        if tracing and now - t_open >= trace_seconds:
            win.traced_s = now - t_open
            mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
            t_open += time.perf_counter() - now
        due = t_open + r["due"]
        now = time.perf_counter()
        if now < due:
            with _annotate("bench.wait"):
                time.sleep(due - now)
        name = f"tenant{r['tenant']}"
        kw = ({"max_new_tokens": r["new_tokens"]}
              if r["entry"] == "generate" else {})
        start = time.perf_counter()
        try:
            with _annotate("bench.serve" if name in pool.warm
                           else "bench.cold_start"):
                out, _, path = pool.dispatch(name, r["entry"],
                                             r["prompt"][None], **kw)
        except Exception:  # a failed request is counted, not fatal
            traceback.print_exc()
            out, path = None, "failed"
        end = time.perf_counter()
        win.records.append({
            "i": i, "tenant": name, "entry": r["entry"], "path": path,
            "due": r["due"], "start": start - t_open, "end": end - t_open,
            "new_tokens": r["new_tokens"] if r["entry"] == "generate"
            else 0,
            "prompt": r["prompt"], "out": out})
    win.seconds = time.perf_counter() - t_open
    if tracing:  # the window ended before the trace did
        win.traced_s = win.seconds
        mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return win
