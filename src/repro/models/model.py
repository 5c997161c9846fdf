"""Unified LM covering the full architecture zoo.

One implementation drives all ten assigned architectures; family
behaviour comes from ``ArchConfig`` flags.  Layers are grouped into
*periods* (the repeating block pattern, e.g. gemma2's (local, global) or
recurrentgemma's (rglru, rglru, attn_local)); parameters for each period
position are stacked over ``n_periods`` and the stack is driven by
``jax.lax.scan`` so the lowered HLO contains one period regardless of
depth.  Layers that do not fill a whole period (gemma3: 62 = 10*6 + 2)
are unrolled as remainder layers.

Public surface:
  block_pattern_of(cfg)   -> per-period block kinds
  model_template(cfg)     -> pytree of ParamSpec (shapes + logical axes)
  init_params(cfg, key)   -> parameter pytree
  init_cache(cfg, B, len) -> decode-state pytree (KV / recurrent states)
  forward(cfg, params, tokens, ...)         -> (hidden, aux)
  loss_fn(cfg, params, batch)               -> (loss, metrics)
  prefill(cfg, params, tokens, ...)         -> (logits, cache)
  decode_step(cfg, params, token, pos, cache) -> (logits, new_cache)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.models.layers import ParamSpec
from repro.models.partition import constrain

f32 = jnp.float32


# --------------------------------------------------------------------------
# block pattern / layer layout
# --------------------------------------------------------------------------

def block_pattern_of(cfg: ArchConfig) -> tuple[str, ...]:
    if cfg.block_pattern:
        return tuple(cfg.block_pattern)
    if cfg.window_pattern:
        return tuple("attn_local" if w == "local" else "attn_global"
                     for w in cfg.window_pattern)
    return ("attn_global",)


def layer_layout(cfg: ArchConfig) -> tuple[tuple[str, ...], int, int]:
    """(pattern, n_periods, n_remainder)."""
    pat = block_pattern_of(cfg)
    return pat, cfg.n_layers // len(pat), cfg.n_layers % len(pat)


def _has_mlp(cfg: ArchConfig, kind: str) -> bool:
    return cfg.d_ff > 0 or cfg.moe is not None


def _has_cross(cfg: ArchConfig) -> bool:
    return cfg.encoder_layers > 0


# --------------------------------------------------------------------------
# templates
# --------------------------------------------------------------------------

def block_template(cfg: ArchConfig, kind: str, *, encoder=False):
    D = cfg.d_model
    norm = lambda: ParamSpec((D,), ("embed",), init="zeros")
    t: dict[str, Any] = {"ln1": norm()}
    if kind.startswith("attn"):
        t["attn"] = L.attn_template(cfg)
        if cfg.sandwich_norm:
            t["ln1_post"] = norm()
    elif kind == "rglru":
        t["rglru"] = L.rglru_template(cfg)
    elif kind == "mlstm":
        t["mlstm"] = L.mlstm_template(cfg)
    elif kind == "slstm":
        t["slstm"] = L.slstm_template(cfg)
    else:
        raise ValueError(kind)
    if not encoder and _has_cross(cfg):
        t["ln_cross"] = norm()
        t["cross"] = L.attn_template(cfg, cross=True)
    if _has_mlp(cfg, kind):
        t["ln2"] = norm()
        if cfg.moe is not None and not encoder:
            t["moe"] = L.moe_template(cfg)
        else:
            t["mlp"] = L.mlp_template(cfg)
        if cfg.sandwich_norm:
            t["ln2_post"] = norm()
    return t


def _stack_specs(tmpl, n):
    # the init scale is pinned from the unstacked shape: the new leading
    # "layers" axis is not the fan-in
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.std()), tmpl,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def model_template(cfg: ArchConfig):
    D, V = cfg.d_model, cfg.vocab
    pat, n_per, n_rem = layer_layout(cfg)
    t: dict[str, Any] = {
        "embed": ParamSpec((V, D), ("vocab", "embed"), scale=1.0),
        "final_norm": ParamSpec((D,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    if cfg.learned_pos_embed:
        t["pos_embed"] = ParamSpec((cfg.learned_pos_embed, D),
                                   (None, "embed"), scale=0.02)
    if cfg.vision_tokens:
        t["vision_proj"] = ParamSpec((D, D), ("embed", "embed"))
    layers_t: dict[str, Any] = {}
    if n_per > 0:
        layers_t["scan"] = {
            f"pos{i}": _stack_specs(block_template(cfg, k), n_per)
            for i, k in enumerate(pat)}
    if n_rem:
        # remainder layers (gemma3: 62 = 10*6 + 2) are a second stacked
        # group scanned once — unstacked layers would take a different
        # GSPMD path for their grads/optimizer state (observed: full-size
        # fp32 replication)
        layers_t["rem_scan"] = {
            f"pos{j}": _stack_specs(block_template(cfg, pat[j]), 1)
            for j in range(n_rem)}
    t["layers"] = layers_t
    if cfg.encoder_layers:
        t["encoder"] = {
            "scan": {"pos0": _stack_specs(
                block_template(cfg, "attn_bidir", encoder=True),
                cfg.encoder_layers)},
            "final_norm": ParamSpec((D,), ("embed",), init="zeros"),
        }
    return t


def init_params(cfg: ArchConfig, key):
    tmpl = model_template(cfg)
    leaves, treedef = jax.tree.flatten(
        tmpl, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    dtype = cfg.jdtype
    return jax.tree.unflatten(
        treedef, [s.initializer(k, dtype) for s, k in zip(leaves, keys)])


def logical_axes(cfg: ArchConfig):
    """Pytree (mirroring params) of logical-axis tuples."""
    return jax.tree.map(lambda s: s.axes, model_template(cfg),
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(cfg: ArchConfig) -> int:
    tmpl = model_template(cfg)
    return sum(math.prod(s.shape) for s in jax.tree.leaves(
        tmpl, is_leaf=lambda x: isinstance(x, ParamSpec)))


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def _block_cache(cfg: ArchConfig, kind: str, B: int, cache_len: int):
    K, hd, D = cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    dt = cfg.jdtype
    if kind == "attn_global":
        S = cache_len
    elif kind == "attn_local":
        S = min(cfg.window_size, cache_len)
    if kind.startswith("attn"):
        quant = cfg.kv_quant == "int8" and kind == "attn_global"
        kv_dt = jnp.int8 if quant else dt
        c = {"k": jnp.zeros((B, S, K, hd), kv_dt),
             "v": jnp.zeros((B, S, K, hd), kv_dt),
             "pos": jnp.full((B, S), -1, jnp.int32)}
        if quant:
            c["k_scale"] = jnp.zeros((B, S, K), f32)
            c["v_scale"] = jnp.zeros((B, S, K), f32)
    elif kind == "rglru":
        R = cfg.rglru_dim or D
        c = {"h": jnp.zeros((B, R), f32),
             "conv": jnp.zeros((B, cfg.conv_width - 1, R), dt)}
    elif kind == "mlstm":
        nh = cfg.lru_heads or cfg.n_heads
        dh = D // nh
        c = {"C": jnp.zeros((B, nh, dh, dh), f32),
             "n": jnp.zeros((B, nh, dh), f32),
             "m": jnp.zeros((B, nh), f32)}
    elif kind == "slstm":
        nh = cfg.lru_heads or cfg.n_heads
        dh = D // nh
        c = {"c": jnp.zeros((B, nh, dh), f32),
             "n": jnp.full((B, nh, dh), 1e-6, f32),
             "h": jnp.zeros((B, nh, dh), f32),
             "m": jnp.zeros((B, nh, dh), f32)}  # per-unit stabilizer
    else:
        raise ValueError(kind)
    if _has_cross(cfg):
        c["cross_k"] = jnp.zeros((B, cfg.encoder_seq, K, hd), dt)
        c["cross_v"] = jnp.zeros((B, cfg.encoder_seq, K, hd), dt)
    return c


def init_cache(cfg: ArchConfig, batch: int, cache_len: int):
    pat, n_per, n_rem = layer_layout(cfg)

    def stack(c, n):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), c)

    cache: dict[str, Any] = {}
    if n_per > 0:
        cache["scan"] = {
            f"pos{i}": stack(_block_cache(cfg, k, batch, cache_len),
                             n_per)
            for i, k in enumerate(pat)}
    if n_rem:
        cache["rem_scan"] = {
            f"pos{j}": stack(_block_cache(cfg, pat[j], batch, cache_len),
                             1)
            for j in range(n_rem)}
    return cache


# --------------------------------------------------------------------------
# block application
# --------------------------------------------------------------------------

def _apply_block(p, cfg, kind, x, positions, *, cache=None, decode=False,
                 make_cache=0, enc_out=None, layer=None):
    """One residual block.  Returns (x, new_cache, aux).  ``layer``: the
    index into the whole expert stacks that ``p["moe"]`` then holds
    (see ``_decode_layers_inplace``)."""
    aux = {}
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = dict(cache) if cache is not None else None
    # named scopes tag the device ops (``attn``/``<kind>``, ``cross``,
    # ``moe``/``mlp``, ``head``) in the compiled op metadata
    with jax.named_scope("attn" if kind.startswith("attn") else kind):
        if kind.startswith("attn"):
            if decode:
                kv_keys = [k for k in cache
                           if not k.startswith("cross")]
                y, kv = L.attn_decode(p["attn"], cfg, h, positions,
                                      {k: cache[k] for k in kv_keys},
                                      kind=kind)
                new_cache.update(kv)
            else:
                y, kv = L.attn_apply(p["attn"], cfg, h, positions, kind=kind,
                                     make_cache=make_cache)
                if make_cache:
                    new_cache = kv
        elif kind == "rglru":
            if decode:
                y, st = L.rglru_decode(p["rglru"], cfg, h,
                                       {k: cache[k] for k in ("h", "conv")})
                new_cache.update(st)
            else:
                y, st = L.rglru_apply(p["rglru"], cfg, h,
                                      make_cache=bool(make_cache))
                if make_cache:
                    new_cache = st
        elif kind == "mlstm":
            if decode:
                y, st = L.mlstm_decode(p["mlstm"], cfg, h,
                                       {k: cache[k] for k in ("C", "n", "m")})
                new_cache.update(st)
            else:
                y, st = L.mlstm_apply(p["mlstm"], cfg, h,
                                      make_cache=bool(make_cache))
                if make_cache:
                    new_cache = st
        elif kind == "slstm":
            if decode:
                y, st = L.slstm_decode(p["slstm"], cfg, h,
                                       {k: cache[k]
                                        for k in ("c", "n", "h", "m")})
                new_cache.update(st)
            else:
                y, st = L.slstm_apply(p["slstm"], cfg, h,
                                      make_cache=bool(make_cache))
                if make_cache:
                    new_cache = st
    if cfg.sandwich_norm and kind.startswith("attn"):
        y = L.rms_norm(y, p["ln1_post"], cfg.norm_eps)
    x = x + y

    if "cross" in p:
        h = L.rms_norm(x, p["ln_cross"], cfg.norm_eps)
        with jax.named_scope("cross"):
            if decode:
                y, _ = L.attn_decode(
                    p["cross"], cfg, h, positions, cache, kind="attn_cross",
                    encoder_kv=(cache["cross_k"], cache["cross_v"]))
            else:
                ek = L.dot(enc_out, p["cross"]["wk"]).reshape(
                    enc_out.shape[0], enc_out.shape[1], cfg.n_kv_heads,
                    cfg.head_dim)
                ev = L.dot(enc_out, p["cross"]["wv"]).reshape(ek.shape)
                if cfg.qkv_bias:
                    ek = ek + p["cross"]["bk"].reshape(ek.shape[-2:])
                    ev = ev + p["cross"]["bv"].reshape(ev.shape[-2:])
                y, _ = L.attn_apply(p["cross"], cfg, h, positions,
                                    kind="attn_cross", encoder_kv=(ek, ev))
                if make_cache:
                    new_cache["cross_k"] = ek
                    new_cache["cross_v"] = ev
        x = x + y

    if "mlp" in p or "moe" in p:
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            with jax.named_scope("moe"):
                y, aux = L.moe_apply(p["moe"], cfg, h, layer=layer)
        else:
            with jax.named_scope("mlp"):
                y = L.mlp_apply(p["mlp"], h)
        if cfg.sandwich_norm:
            y = L.rms_norm(y, p["ln2_post"], cfg.norm_eps)
        x = x + y
    return x, new_cache, aux


def _zero_aux(cfg):
    if cfg.moe is None:
        return {}
    return {"expert_load": jnp.zeros((cfg.moe.n_experts,), f32),
            "moe_aux_loss": jnp.zeros((), f32)}


def constrain_like_params(cfg: ArchConfig, tree):
    """Pin a params-shaped pytree (e.g. grads, fp32 accumulators) to the
    parameter sharding — no-op outside a mesh context."""
    tmpl = model_template(cfg)
    return jax.tree.map(
        lambda arr, spec: constrain(arr, *spec.axes), tree, tmpl,
        is_leaf=lambda t: isinstance(t, ParamSpec))


def _constrain_block_params(cfg, kind, p):
    """Pin block params (and, via the transpose, their grads) to their
    logical sharding."""
    tmpl = block_template(cfg, kind)
    return jax.tree.map(
        lambda arr, spec: constrain(arr, *spec.axes), p, tmpl,
        is_leaf=lambda t: isinstance(t, ParamSpec))


def _decode_layers_inplace(cfg, params_scan, x, positions, caches_scan,
                           pattern, n):
    """Decode path: fori_loop with the full stacked caches as carry.

    Caches are updated with dynamic_update_index_in_dim so XLA keeps the
    multi-GB KV buffers in place through the while loop (a scan emitting
    new caches as ys would double-buffer them).  The expert weights stay
    whole stacks indexed by the layer inside ``moe_apply``: XLA fuses a
    slice of them into the capacity einsums, but not into the routed
    kernel, which would then copy one layer's experts per step.
    """
    def at(tree, t):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, t, 0, keepdims=False),
            tree)

    def block_at(p, t):
        out = at({k: v for k, v in p.items() if k != "moe"}, t)
        if "moe" in p:
            out["moe"] = {k: v if k in ("wi", "wo") else at(v, t)
                          for k, v in p["moe"].items()}
        return out

    def body(t, carry):
        x, caches = carry
        for i, kind in enumerate(pattern):
            c_t = at(caches[f"pos{i}"], t)
            x, nc, _ = _apply_block(block_at(params_scan[f"pos{i}"], t),
                                    cfg, kind, x, positions, cache=c_t,
                                    decode=True, layer=t)
            # write back only entries the block actually changed —
            # re-writing static slices (whisper's cross K/V: ~2 GB per
            # layer) would force XLA to copy them every loop iteration
            grp = dict(caches[f"pos{i}"])
            for key, new in nc.items():
                if new is c_t[key] and cfg.decode_skip_static_writes:
                    continue
                grp[key] = lax.dynamic_update_index_in_dim(
                    grp[key], new.astype(grp[key].dtype), t, 0)
            caches = {**caches, f"pos{i}": grp}
        return (x, caches)

    return lax.fori_loop(0, n, body, (x, caches_scan))


def _scan_group(cfg, params_scan, caches_scan, pattern, x, positions, *,
                decode=False, make_cache=0, enc_out=None, remat=False):
    """Run one stacked layer group (the main periods or the remainder).

    Returns (x, new_caches, aux).  Training/prefill drive a lax.scan with
    per-block remat; decode drives the in-place fori_loop above.
    """
    n = jax.tree.leaves(params_scan)[0].shape[0]
    if decode and cfg.scan_layers:
        x, new_scan = _decode_layers_inplace(
            cfg, params_scan, x, positions, caches_scan, pattern, n)
        return x, new_scan, _zero_aux(cfg)

    # remat granularity is one *block*, not one period: a multi-block
    # period (gemma3: 6, recurrentgemma: 3) checkpointed as a unit would
    # keep the whole period's intermediates live during its backward
    blk = partial(_apply_block, decode=decode, make_cache=make_cache,
                  enc_out=enc_out)
    if remat:
        blk = jax.checkpoint(blk, static_argnums=(1, 2))

    def body(carry, per_layer):
        x = carry
        x = constrain(x, "batch", "seq", "embed")
        p_stk, c_stk = per_layer
        new_cs, aux_acc = {}, _zero_aux(cfg)
        for i, kind in enumerate(pattern):
            c = c_stk.get(f"pos{i}") if c_stk is not None else None
            x, nc, aux = blk(p_stk[f"pos{i}"], cfg, kind, x, positions,
                             cache=c)
            new_cs[f"pos{i}"] = nc if nc is not None else 0
            for k in aux_acc:
                aux_acc[k] = aux_acc[k] + aux.get(k, 0)
        return x, (new_cs, aux_acc)

    xs = (params_scan, caches_scan) if caches_scan is not None \
        else (params_scan, None)
    aux_tot = _zero_aux(cfg)
    if cfg.scan_layers:
        x, (new_scan, aux_stk) = lax.scan(body, x, xs)
        aux_tot = {k: aux_tot[k] + aux_stk[k].sum(0) for k in aux_tot}
    else:  # unrolled (perf-iteration comparison point)
        new_list = []
        for t in range(n):
            sl = jax.tree.map(lambda a: a[t], xs)
            x, (nc, aux) = body(x, sl)
            new_list.append(nc)
            aux_tot = {k: aux_tot[k] + aux[k] for k in aux_tot}
        new_scan = jax.tree.map(lambda *a: jnp.stack(a), *new_list) \
            if new_list and (make_cache or decode) else {}
    if not (make_cache or decode):
        new_scan = {}
    return x, new_scan, aux_tot


def _run_layers(cfg, params_l, x, positions, *, caches=None, decode=False,
                make_cache=0, enc_out=None, remat=False):
    """Drive the stacked layer groups.  Returns (x, new_caches, aux)."""
    pat, n_per, n_rem = layer_layout(cfg)
    aux_tot = _zero_aux(cfg)
    new_caches: dict[str, Any] = {}
    for group, pattern in (("scan", pat), ("rem_scan", pat[:n_rem])):
        if group not in params_l:
            continue
        c = caches.get(group) if caches else None
        x, new_c, aux = _scan_group(
            cfg, params_l[group], c, pattern, x, positions, decode=decode,
            make_cache=make_cache, enc_out=enc_out, remat=remat)
        if make_cache or decode:
            new_caches[group] = new_c
        aux_tot = {k: aux_tot[k] + aux.get(k, 0) for k in aux_tot}
    return x, new_caches, aux_tot


# --------------------------------------------------------------------------
# encoder (whisper stub frontend -> transformer encoder)
# --------------------------------------------------------------------------

def run_encoder(cfg, params, frames, *, remat=False):
    """frames: (B, encoder_seq, D) precomputed frame embeddings (stub)."""
    x = frames.astype(cfg.jdtype)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, p_stk):
        x = constrain(x, "batch", "seq", "embed")
        h = L.rms_norm(x, p_stk["ln1"], cfg.norm_eps)
        y, _ = L.attn_apply(p_stk["attn"], cfg, h, positions,
                            kind="attn_bidir")
        x = x + y
        h = L.rms_norm(x, p_stk["ln2"], cfg.norm_eps)
        return x + L.mlp_apply(p_stk["mlp"], h), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["encoder"]["scan"]["pos0"])
    return L.rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


# --------------------------------------------------------------------------
# model entry points
# --------------------------------------------------------------------------

def embed_tokens(cfg, params, tokens):
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _head(cfg, params, h):
    with jax.named_scope("head"):
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("...d,dv->...v", h, w,
                            preferred_element_type=f32)
        return L.softcap(logits, cfg.final_softcap)


def forward(cfg: ArchConfig, params, tokens, *, patch_embeds=None,
            enc_frames=None, make_cache=0, remat=False):
    """Full-sequence forward.  Returns (hidden (B,S,D), caches, aux).

    pixtral: `patch_embeds` (B, vision_tokens, D) fill the first
    ``vision_tokens`` positions; `tokens` then has S - vision_tokens ids.
    whisper: `enc_frames` (B, encoder_seq, D) drive the encoder; tokens
    are decoder ids.
    """
    x = embed_tokens(cfg, params, tokens)
    if cfg.vision_tokens and patch_embeds is not None:
        vis = L.dot(patch_embeds.astype(x.dtype), params["vision_proj"])
        x = jnp.concatenate([vis, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.learned_pos_embed:
        x = x + params["pos_embed"][jnp.minimum(
            positions, cfg.learned_pos_embed - 1)]
    enc_out = None
    if cfg.encoder_layers:
        if enc_frames is None:  # text-only traffic on an enc-dec model
            enc_frames = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                   x.dtype)
        enc_out = run_encoder(cfg, params, enc_frames, remat=remat)
    x, caches, aux = _run_layers(
        cfg, params["layers"], x, positions, make_cache=make_cache,
        enc_out=enc_out, remat=remat)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, caches, aux


def loss_fn(cfg: ArchConfig, params, batch):
    """Next-token loss.  batch: tokens (B,S), labels (B,S) with -1 = pad.

    The head+CE runs in token chunks of ``cfg.loss_chunk`` (remat'd) so
    the (tokens, vocab) logits buffer never fully materializes.
    """
    h, _, aux = forward(cfg, params, batch["tokens"],
                        patch_embeds=batch.get("patch_embeds"),
                        enc_frames=batch.get("enc_frames"),
                        remat=cfg.remat == "block")
    labels = batch["labels"]
    if cfg.vision_tokens and batch.get("patch_embeds") is not None:
        pad = jnp.full((labels.shape[0], cfg.vision_tokens), -1,
                       labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    B, S, D = h.shape

    def ce(h_chunk, l_chunk):
        logits = _head(cfg, params, h_chunk)  # (B, s, V) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(l_chunk, 0)[..., None], axis=-1)[..., 0]
        mask = (l_chunk >= 0).astype(f32)
        return ((lse - tgt) * mask).sum(), mask.sum()

    # chunk the head+CE along the *sequence* dim: (B, s_chunk, D) chunks
    # keep the layer-stack's (batch, seq) sharding, so no resharding is
    # needed and the fp32 logits buffer is (B, s_chunk, V) / n_devices
    chunk_s = 0
    if cfg.loss_chunk:
        chunk_s = min(S, max(cfg.loss_chunk // max(B, 1), 256))
    if chunk_s and S % chunk_s == 0 and chunk_s < S:
        n = S // chunk_s
        hc = h.reshape(B, n, chunk_s, D).swapaxes(0, 1)
        lc = labels.reshape(B, n, chunk_s).swapaxes(0, 1)
        (tot, cnt) = lax.scan(
            lambda c, xs: (tuple(a + b for a, b in
                                 zip(c, jax.checkpoint(ce)(*xs))), None),
            (jnp.zeros((), f32), jnp.zeros((), f32)), (hc, lc))[0]
    else:
        tot, cnt = ce(h, labels)
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = {"ce_loss": loss}
    if cfg.moe is not None:
        metrics["moe_aux_loss"] = aux["moe_aux_loss"]
        metrics["expert_load"] = aux["expert_load"]
        loss = loss + 0.01 * aux["moe_aux_loss"]
    metrics["loss"] = loss
    return loss, metrics


def prefill(cfg: ArchConfig, params, tokens, *, cache_len=None,
            patch_embeds=None, enc_frames=None):
    """Prefill: forward + decode-cache construction.  Returns
    (last-token logits (B, V), caches, aux)."""
    cache_len = cache_len or tokens.shape[1] + (cfg.vision_tokens or 0)
    h, caches, aux = forward(cfg, params, tokens,
                             patch_embeds=patch_embeds,
                             enc_frames=enc_frames, make_cache=cache_len)
    return _head(cfg, params, h[:, -1]), caches, aux


def decode_step(cfg: ArchConfig, params, token, pos, caches):
    """One decode step.  token: (B, 1) ids; pos: (B,) positions.

    Returns (logits (B, V), new_caches).
    """
    x = embed_tokens(cfg, params, token)
    if cfg.learned_pos_embed:
        x = x + params["pos_embed"][
            jnp.minimum(pos, cfg.learned_pos_embed - 1)][:, None]
    x, new_caches, _ = _run_layers(cfg, params["layers"], x, pos,
                                   caches=caches, decode=True)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head(cfg, params, x[:, 0]), new_caches
