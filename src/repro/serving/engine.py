"""Serverless serving engine with SLIMSTART-guided cold starts.

Cold-start anatomy (the Level-B "library loading"):
    import -> config -> weight materialization -> entry-point compilation
Each stage is a named ``Component``; the engine materializes the eager
set per ``LoadPolicy``, serves requests (materializing lazy components
on first use, exactly like a deferred import), and tracks per-entry
invocations + per-expert routing mass as the utilization signal for the
profile-guided optimizer (``engine.report()`` -> ``LoadPolicy.from_report``).

:class:`EnginePool` adds the fleet layer on top: pool-aware dispatch
across many models — requests route to a warm engine when one is
resident, fall back to a cold start (building and admitting a fresh
engine, evicting the worst-amortizing one past the budget), and the
pool's ``rewarm`` method plugs into
``SlimStartController(rewarm_fn=...)`` so a re-profile re-derives every
warm engine's load policy from its live utilization.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.layers import moe_paths
from repro.models.model import (
    _head, decode_step, forward, init_cache, init_params, prefill,
)
from repro.obs.tracing import get_tracer
from repro.serving.components import Component, ComponentRegistry, LoadPolicy


# step programs behind the entry points (jitted per engine with the
# leading arguments bound, see ServingEngine.entry_programs)
def _jit_named(fn: Callable, *bound) -> Callable:
    """``jax.jit(partial(fn, *bound))`` whose XLA module is named
    ``jit_<fn.__name__>`` (a bare partial compiles as ``jit__unknown``)."""
    step = partial(fn, *bound)
    step.__name__ = fn.__name__
    return jax.jit(step)


def score_step(cfg: ArchConfig, params, tokens):
    """Teacher-forced logits (B, S, V) for every position."""
    h, _, _ = forward(cfg, params, tokens)
    return _head(cfg, params, h)


def prefill_step(cfg: ArchConfig, cache_len: int, params, tokens, extra):
    logits, caches, aux = prefill(cfg, params, tokens, cache_len=cache_len,
                                  **extra)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    load = aux.get("expert_load") if cfg.moe else None
    return nxt, caches, load


def decode_next(cfg: ArchConfig, params, token, pos, caches):
    logits, caches = decode_step(cfg, params, token, pos, caches)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt[:, None], caches


def _moe_groups(layers) -> list[dict]:
    """The MoE parameter dicts of a layer tree, in a stable order (one
    per stacked layer group)."""
    out = []
    for k, v in sorted(layers.items()):
        if k == "moe":
            out.append(v)
        elif isinstance(v, dict):
            out.extend(_moe_groups(v))
    return out


def _m_engine_dispatch(model: str, path: str) -> None:
    from repro.obs.metrics import default_registry
    default_registry().counter(
        "repro_engine_dispatch_total",
        "EnginePool dispatches by path (warm/cold/queued/shed)",
        labels=("model", "path")).labels(model=model, path=path).inc()


class ServingEngine:
    """One model server instance ("function instance" in FaaS terms)."""

    def __init__(self, cfg: ArchConfig, *, policy: Optional[LoadPolicy]
                 = None, seed: int = 0, batch_size: int = 1,
                 prefill_len: int = 32, max_len: int = 96):
        self.cfg = cfg
        self.policy = policy or LoadPolicy.eager_all()
        self.seed = seed
        self.B = batch_size
        self.prefill_len = prefill_len
        self.max_len = max_len
        self.registry = ComponentRegistry()
        self.entry_counts: dict[str, int] = {}
        self.expert_mass: Optional[np.ndarray] = None
        self._params = None
        self.cold_start_s: Optional[float] = None
        # entry -> the MoE paths its compiled programs took
        self.moe_paths: dict[str, set] = {}
        self._build_components()

    # ------------------------------------------------------------ build
    def _build_components(self):
        cfg = self.cfg
        reg = self.registry
        key = jax.random.PRNGKey(self.seed)

        def weights_builder():
            params = init_params(cfg, key)
            if cfg.moe is not None:
                # expert FF weights are materialized per-expert instead
                params = self._blank_experts(params)
            return params

        reg.add(Component("weights.core", "weights", weights_builder))

        if cfg.moe is not None:
            for e in range(cfg.moe.n_experts):
                reg.add(Component(f"expert.{e}", "experts",
                                  partial(self._expert_builder, e)))
        if cfg.vision_tokens:
            reg.add(Component("frontend.vision", "frontend",
                              lambda: True))  # vision_proj kept in core;
            # the *stub tower* cost is modeled by the patch embedder
        if cfg.encoder_layers:
            reg.add(Component("frontend.audio_encoder", "frontend",
                              lambda: True))

        # per-entry-point compilations (AOT: lower+compile counted as the
        # component's init cost — the Level-B analogue of importing the
        # module that serves this handler)
        for entry in self.entries():
            reg.add(Component(f"compile.{entry}", "compile",
                              partial(self._compile_entry, entry),
                              span_attrs=partial(self._compile_attrs,
                                                 entry)))

    def entries(self) -> list[str]:
        cfg = self.cfg
        out = ["generate"]
        if cfg.vision_tokens:
            out.append("vision_generate")
        if cfg.encoder_layers:
            out.append("transcribe")
        out.append("score")  # rarely-hit scoring/teacher-forcing handler
        return out

    # ---------------------------------------------------------- experts
    def _blank_experts(self, params):
        for moe in _moe_groups(params["layers"]):
            moe["wi"] = jnp.zeros_like(moe["wi"])
            moe["wo"] = jnp.zeros_like(moe["wo"])
        return params

    def _expert_builder(self, e: int):
        """Materialize expert e's FF weights in every MoE layer and patch
        them into the live param tree.  Keys fold in only stable integer
        indices (expert, layer group, weight), so every process builds
        the same weights."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), 1000 + e)
        for g, moe in enumerate(_moe_groups(self._params["layers"])):
            for i, w in enumerate(("wi", "wo")):
                shape = moe[w].shape  # (n_stack, E, ...)
                k = jax.random.fold_in(jax.random.fold_in(key, g), i)
                sub = jax.random.normal(k, shape[:1] + shape[2:],
                                        jnp.float32)
                sub = (sub / np.sqrt(shape[2])).astype(moe[w].dtype)
                moe[w] = moe[w].at[:, e].set(sub)
        return e

    # ------------------------------------------------------ compilation
    def _entry_shapes(self, entry: str):
        cfg = self.cfg
        B = self.B
        toks = jax.ShapeDtypeStruct((B, self.prefill_len), jnp.int32)
        extras = {}
        if entry == "vision_generate" and cfg.vision_tokens:
            extras["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), cfg.jdtype)
        if entry == "transcribe" and cfg.encoder_layers:
            extras["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), cfg.jdtype)
        return toks, extras

    def entry_programs(self, entry: str) -> dict[str, tuple[Callable,
                                                            tuple]]:
        """The jitted step programs that serve ``entry``, each with the
        abstract arguments it is compiled for (shapes only, no
        sharding: the default device places them)."""
        cfg = self.cfg
        toks, extras = self._entry_shapes(entry)
        params = self._param_shapes()
        if entry == "score":
            return {"score": (_jit_named(score_step, cfg), (params, toks))}
        cache_len = self.max_len + (cfg.vision_tokens or 0)
        caches = jax.eval_shape(lambda: init_cache(cfg, self.B, cache_len))
        return {
            "prefill": (_jit_named(prefill_step, cfg, cache_len),
                        (params, toks, extras)),
            "decode": (_jit_named(decode_next, cfg),
                       (params,
                        jax.ShapeDtypeStruct((self.B, 1), jnp.int32),
                        jax.ShapeDtypeStruct((self.B,), jnp.int32),
                        caches)),
        }

    def _compile_entry(self, entry: str):
        with moe_paths() as paths:
            exes = {name: fn.lower(*args).compile()
                    for name, (fn, args) in self.entry_programs(entry).items()}
        self.moe_paths[entry] = paths
        return exes

    def _compile_attrs(self, entry: str) -> dict:
        """``moe_path`` of an entry's compile: ``routed`` where one of its
        programs reads only the routed experts (decode at batch 1),
        ``capacity`` where all its MoE layers dispatch by capacity;
        absent without MoE."""
        paths = self.moe_paths.get(entry)
        if not paths:
            return {}
        return {"moe_path": "routed" if "routed" in paths else "capacity"}

    def _param_shapes(self):
        return jax.eval_shape(
            lambda: init_params(self.cfg, jax.random.PRNGKey(0)))

    # ---------------------------------------------------------- serving
    def cold_start(self, ctx: Optional[dict] = None):
        """Materialize the eager set; returns wall seconds.  ``ctx``
        parents the ``engine_cold_start`` span (the pool's
        ``cold_start``)."""
        with get_tracer().span("engine_cold_start", ctx=ctx) as sp:
            t0 = time.perf_counter()
            weights = self.registry["weights.core"]
            self._params = weights.get(sp.ctx())
            weights.uses -= 1
            self.registry.materialize_eager(self.policy, sp.ctx())
            self.cold_start_s = time.perf_counter() - t0
        return self.cold_start_s

    def serve(self, entry: str, tokens: np.ndarray, *,
              max_new_tokens: int = 8, extras: Optional[dict] = None,
              ctx: Optional[dict] = None):
        """Serve one batched request; returns (tokens_out, latency_s).

        Spans: ``engine_serve`` (``ctx`` parents it) and, for a
        generating entry, its children ``engine_prefill`` (input
        transfer and the prefill program), ``engine_route`` (the
        router-load read-back, MoE only), ``engine_decode`` (the host
        token loop) and ``engine_readback`` (the tokens to the host)."""
        t0 = time.perf_counter()
        new_tokens = 0 if entry == "score" else max_new_tokens
        with get_tracer().span("engine_serve", ctx=ctx, entry=entry,
                               new_tokens=new_tokens) as sp:
            out = self._serve(entry, tokens, max_new_tokens, extras,
                              sp.ctx())
        return out, time.perf_counter() - t0

    def _serve(self, entry: str, tokens: np.ndarray, max_new_tokens: int,
               extras: Optional[dict], ctx: Optional[dict]):
        tracer = get_tracer()
        cfg = self.cfg
        self.entry_counts[entry] = self.entry_counts.get(entry, 0) + 1
        weights = self.registry["weights.core"]
        if self._params is None:
            self._params = weights.get(ctx)
            weights.uses -= 1  # counted below
        exes = self.registry[f"compile.{entry}"].get(ctx)
        if entry == "vision_generate":
            self.registry["frontend.vision"].get(ctx)
        if entry == "transcribe":
            self.registry["frontend.audio_encoder"].get(ctx)

        weights.uses += 1  # every request hits them
        if entry == "score":
            out = exes["score"](self._params, jnp.asarray(tokens, jnp.int32))
            jax.block_until_ready(out)
            return np.asarray(out)

        with tracer.span("engine_prefill", ctx=ctx):
            toks = jnp.asarray(tokens, jnp.int32)
            extra = dict(extras or {})
            _, extra_shapes = self._entry_shapes(entry)
            for k, sds in extra_shapes.items():
                if k not in extra:
                    extra[k] = jnp.zeros(sds.shape, sds.dtype)
            nxt, caches, load = exes["prefill"](self._params, toks, extra)
        if load is not None:
            with tracer.span("engine_route", ctx=ctx) as sp:
                self._account_experts(np.asarray(load), sp.ctx())
        vt = cfg.vision_tokens if entry == "vision_generate" else 0
        pos0 = toks.shape[1] + (vt or 0)
        out = [nxt]
        tok = nxt[:, None]
        with tracer.span("engine_decode", ctx=ctx,
                         steps=max_new_tokens - 1):
            for i in range(max_new_tokens - 1):
                pos = jnp.full((self.B,), pos0 + i, jnp.int32)
                tok, caches = exes["decode"](self._params, tok, pos, caches)
                out.append(tok[:, 0])
        with tracer.span("engine_readback", ctx=ctx):
            return np.stack([np.asarray(o) for o in out], axis=1)

    # ----------------------------------------- utilization / SLIMSTART
    def _account_experts(self, load: np.ndarray,
                         ctx: Optional[dict] = None):
        """Routing mass -> expert Component.uses; materialize experts
        that received traffic but are still cold (lazy loading)."""
        if self.expert_mass is None:
            self.expert_mass = np.zeros_like(load)
        self.expert_mass += load
        for e, mass in enumerate(load):
            name = f"expert.{e}"
            if name in self.registry and mass > 0:
                comp = self.registry[name]
                if not comp.ready:
                    comp.get(ctx)  # deferred materialization on first route
                else:
                    comp.uses += 1

    def report(self) -> dict:
        rep = self.registry.report()
        rep["entry_counts"] = dict(self.entry_counts)
        rep["cold_start_s"] = self.cold_start_s
        if self.expert_mass is not None:
            tot = float(self.expert_mass.sum()) or 1.0
            rep["expert_utilization"] = {
                f"expert.{e}": round(float(m) / tot, 4)
                for e, m in enumerate(self.expert_mass)}
            # fold routing mass into component utilization rows
            for row in rep["components"]:
                if row["component"].startswith("expert."):
                    row["utilization"] = rep["expert_utilization"].get(
                        row["component"], 0.0)
        return rep


class PoolSaturated(RuntimeError):
    """Backpressure: a model's cold-start wait queue is full, the
    request was shed instead of piling more load on a cold pool."""


class EnginePool:
    """Pool-aware dispatch across warm :class:`ServingEngine` instances.

    The Level-B analogue of the zygote fleet
    (:class:`repro.pool.fleet.ZygoteFleet`): each *model* is an app,
    a warm engine is a resident instance, and ``max_warm`` is the shared
    budget.  ``dispatch`` routes a request to the model's warm engine;
    on a miss it cold-starts a fresh engine (``builders[model]``), and
    past the budget it evicts the warm engine that amortizes worst —
    fewest cold-start milliseconds saved per dispatch since admission —
    dropping its components so the memory is actually released.

    ``queue_depth`` turns on **queue-aware dispatch** for concurrent
    callers: while one thread cold-starts a model, other requests for
    the same model *wait* for that one engine instead of each building
    a duplicate (single-flight), at most ``queue_depth`` of them — the
    next raises :class:`PoolSaturated` and is counted as a shed.
    Waiters return with path ``"queued"`` and their wait recorded in
    ``queue_waits_s``.  ``queue_depth=None`` (default) keeps the
    legacy single-threaded behavior.
    """

    def __init__(self, builders: dict[str, Callable[[], "ServingEngine"]],
                 *, max_warm: int = 2,
                 queue_depth: Optional[int] = None,
                 fault_hook=None) -> None:
        if max_warm < 1:
            raise ValueError("max_warm must be >= 1")
        if queue_depth is not None and queue_depth < 0:
            raise ValueError("queue_depth must be >= 0")
        # chaos hook (repro.pool.chaos), called at the engine cold-start
        # site; None (default) leaves dispatch untouched
        self.fault_hook = fault_hook
        self.builders = dict(builders)
        self.max_warm = max_warm
        self.queue_depth = queue_depth
        self.warm: dict[str, ServingEngine] = {}
        self.hits = 0
        self.misses = 0
        self.sheds = 0
        self.evictions: list[str] = []
        self.queue_waits_s: list[float] = []
        self._dispatches: dict[str, int] = {}
        self._lock = threading.Lock()
        # model -> Event set once its in-flight cold start finishes
        self._cold_events: dict[str, threading.Event] = {}
        self._cold_waiters: dict[str, int] = {}
        # queue mode only: engines with serves in flight must not have
        # their components dropped under them by a concurrent eviction
        # — the drop is deferred until the last serve returns
        self._serving: dict[int, int] = {}          # id(engine) -> count
        self._drop_pending: dict[int, "ServingEngine"] = {}

    # ----------------------------------------------------------- dispatch
    def dispatch(self, model: str, entry: str, tokens, **kw):
        """Serve one request; returns ``(output, latency_s, path)`` with
        ``path`` in {"warm", "cold", "queued"}.  Cold latency includes
        the engine's cold start, exactly like a FaaS cold invocation;
        queued latency includes the wait for the in-flight one."""
        if model not in self.builders:
            raise KeyError(f"unknown model {model!r}")
        tracer = get_tracer()
        with tracer.span("engine_dispatch", model=model,
                         entry=entry) as sp:
            try:
                if self.queue_depth is None:
                    out, lat, path = self._dispatch_unlocked(
                        model, entry, tokens, _ctx=sp.ctx(), **kw)
                else:
                    out, lat, path = self._dispatch_queued(
                        model, entry, tokens, _ctx=sp.ctx(), **kw)
            except PoolSaturated:
                sp.set("path", "shed")
                _m_engine_dispatch(model, "shed")
                raise
            sp.set("path", path)
            _m_engine_dispatch(model, path)
            return out, lat, path

    def _dispatch_unlocked(self, model: str, entry: str, tokens,
                           _ctx: Optional[dict] = None, **kw):
        eng = self.warm.get(model)
        if eng is not None:
            self.hits += 1
            self._dispatches[model] = self._dispatches.get(model, 0) + 1
            out, lat = eng.serve(entry, tokens, ctx=_ctx, **kw)
            return out, lat, "warm"
        self.misses += 1
        with get_tracer().span("cold_start", ctx=_ctx, model=model) as cs:
            if self.fault_hook is not None:
                self.fault_hook("cold_start", app=model)
            eng = self.builders[model]()
            cold_s = eng.cold_start(ctx=cs.ctx())
        self._admit(model, eng)
        self._dispatches[model] = self._dispatches.get(model, 0) + 1
        out, lat = eng.serve(entry, tokens, ctx=_ctx, **kw)
        return out, lat + cold_s, "cold"

    def _dispatch_queued(self, model: str, entry: str, tokens,
                         _ctx: Optional[dict] = None, **kw):
        t0 = time.perf_counter()
        waited = False
        wait_s = 0.0
        while True:
            evt: Optional[threading.Event] = None
            with self._lock:
                eng = self.warm.get(model)
                if eng is not None:
                    self.hits += 1
                    self._dispatches[model] = \
                        self._dispatches.get(model, 0) + 1
                    if waited:
                        wait_s = time.perf_counter() - t0
                        self.queue_waits_s.append(wait_s)
                    path = "queued" if waited else "warm"
                elif model not in self._cold_events:
                    # we are the builder: single-flight the cold start
                    self._cold_events[model] = threading.Event()
                    path = "build"
                else:
                    if self._cold_waiters.get(model, 0) \
                            >= self.queue_depth:
                        self.sheds += 1
                        raise PoolSaturated(
                            f"model {model!r}: {self.queue_depth} "
                            f"requests already wait on its cold start")
                    self._cold_waiters[model] = \
                        self._cold_waiters.get(model, 0) + 1
                    evt = self._cold_events[model]
                    path = "wait"
            if path in ("warm", "queued"):
                out, lat = self._serve_counted(eng, entry, tokens,
                                               ctx=_ctx, **kw)
                return out, lat + wait_s, path
            if path == "build":
                try:
                    with get_tracer().span("cold_start", ctx=_ctx,
                                           model=model) as cs:
                        if self.fault_hook is not None:
                            self.fault_hook("cold_start", app=model)
                        eng = self.builders[model]()
                        cold_s = eng.cold_start(ctx=cs.ctx())
                    with self._lock:
                        self.misses += 1
                        self._admit(model, eng)
                        self._dispatches[model] = \
                            self._dispatches.get(model, 0) + 1
                finally:
                    # wake waiters even on a failed build — one of them
                    # retries as the next builder
                    with self._lock:
                        self._cold_events.pop(model).set()
                out, lat = self._serve_counted(eng, entry, tokens,
                                               ctx=_ctx, **kw)
                return out, lat + cold_s, "cold"
            # path == "wait": block until the in-flight build finishes
            evt.wait()
            with self._lock:
                self._cold_waiters[model] = max(
                    self._cold_waiters.get(model, 1) - 1, 0)
            waited = True

    def _serve_counted(self, eng: "ServingEngine", entry: str, tokens,
                       **kw):
        """Serve while holding an in-flight ticket on the engine so a
        concurrent eviction defers its component drop (queue mode)."""
        key = id(eng)
        with self._lock:
            self._serving[key] = self._serving.get(key, 0) + 1
        try:
            return eng.serve(entry, tokens, **kw)
        finally:
            with self._lock:
                n = self._serving.get(key, 1) - 1
                if n > 0:
                    self._serving[key] = n
                else:
                    self._serving.pop(key, None)
                    pending = self._drop_pending.pop(key, None)
                    if pending is not None:
                        for comp in pending.registry.values():
                            comp.drop()

    def _admit(self, model: str, eng: "ServingEngine") -> None:
        while len(self.warm) >= self.max_warm:
            victim = min(self.warm, key=self._amortization)
            dropped = self.warm.pop(victim)
            if self._serving.get(id(dropped), 0) > 0:
                # a thread is mid-serve on the victim: dropping its
                # components now would yield None mid-request — defer
                # to the last in-flight serve's exit
                self._drop_pending[id(dropped)] = dropped
            else:
                for comp in dropped.registry.values():
                    comp.drop()
            self.evictions.append(victim)
            # a re-admitted model must not inherit its old residency's
            # dispatch count, or its amortization score starts inflated
            self._dispatches.pop(victim, None)
        # a builder may hand back the same engine object that was
        # evicted earlier (cached/singleton builders): cancel any
        # still-pending deferred drop or it would fire after this
        # re-admission and gut a warm engine
        self._drop_pending.pop(id(eng), None)
        self.warm[model] = eng

    def _amortization(self, model: str) -> float:
        """Cold-start seconds this engine saves per dispatch it served —
        low means the warm slot is wasted on it."""
        eng = self.warm[model]
        cold_s = eng.cold_start_s or 0.0
        return cold_s * self._dispatches.get(model, 0)

    # ------------------------------------------------------ adaptive hook
    def shared_hot_components(self, *, min_models: int = 2,
                              util_threshold: float = 0.02) -> list[str]:
        """The Level-B analogue of the fleet's cross-app shared hot set
        (:mod:`repro.pool.sharing`): component names hot (utilization
        >= threshold) for at least ``min_models`` of the warm engines.
        A fresh cold start's policy prewarms these even when its own
        model has no utilization history yet — the pool-wide base
        layer every member keeps paying for anyway."""
        from repro.pool.sharing import intersect_hot_sets
        hot_sets = {}
        for model, eng in self.warm.items():
            report = getattr(eng, "report", None)
            if report is None:  # duck-typed engine without utilization
                continue
            rep = report()
            hot_sets[model] = [row["component"]
                               for row in rep["components"]
                               if row["utilization"] >= util_threshold]
        # component names are a flat namespace ("expert.1"/"expert.2"
        # share no loadable parent): exact-name intersection only
        return sorted(intersect_hot_sets(hot_sets,
                                         min_members=min_models,
                                         prefixes=False))

    def rewarm(self, report=None) -> dict:
        """``SlimStartController.rewarm_fn`` hook: after a re-profile,
        re-derive every warm engine's :class:`LoadPolicy` from its own
        live utilization report — *plus* the pool's shared hot
        components (see :meth:`shared_hot_components`), so a component
        the rest of the pool keeps hot is never deferred by one
        engine's thin local history — and materialize the new set.

        ``report`` takes anything :func:`repro.api.as_report` accepts
        (an :class:`~repro.core.profiler.report.OptimizationReport` or
        a saved versioned artifact path) for signature compatibility
        with the Level-A hooks; Level-B utilization lives in the warm
        engines themselves, so the artifact is validated but its
        contents are not consulted."""
        if report is not None:
            from repro.api.artifacts import as_report
            as_report(report)  # validate/normalize; Level-B ignores it
        from repro.serving.components import LoadPolicy
        shared = frozenset(self.shared_hot_components())
        out = {}
        for model, eng in self.warm.items():
            policy = LoadPolicy.from_report(eng.report())
            policy = LoadPolicy(
                lazy_groups=policy.lazy_groups,
                lazy_names=policy.lazy_names - shared,
                prewarm=policy.prewarm
                | {c for c in shared if c in eng.registry})
            eng.policy = policy
            eng.registry.materialize_eager(policy)
            out[model] = sorted(policy.prewarm)
        return out

    def stats(self) -> dict:
        total = self.hits + self.misses
        waits = sorted(self.queue_waits_s)
        return {
            "warm_models": sorted(self.warm),
            "shared_hot_components": self.shared_hot_components(),
            "hits": self.hits,
            "misses": self.misses,
            "hit_ratio": self.hits / max(total, 1),
            "evictions": list(self.evictions),
            "sheds": self.sheds,
            # every EnginePool shed has one cause; keyed like the fleet
            # summary's breakdown so dashboards can merge the two
            "shed_reasons": ({"pool-saturated": self.sheds}
                             if self.sheds else {}),
            "coalesced": len(self.queue_waits_s),
            "queue_wait_p99_s": (
                waits[min(len(waits) - 1,
                          max(0, round(0.99 * (len(waits) - 1))))]
                if waits else 0.0),
        }
