"""What decides ``correct``: the served tokens against the plain float32
reference.

Once the window has closed and the pool is freed, a sample of the
window's requests drawn from the seed (every ``score``, the longest
``generate``, the first request of up to four engines cold-started in
the window, then others up to a budget of decoded tokens) is run
through the reference of the configuration, one forward per request
over its prompt and served tokens, with the tenant's weights rebuilt
from its seed.  At every served position the reading is the gap by which the
reference's logit of the served token lies below the reference's best
logit there: 0 where the served token is the reference's first choice.
For ``score`` the served token at a position is the one the served
logits put first.  The first number compared is the widest gap.

``generate`` returns tokens only, and with random weights and tied
embeddings the first choice is often a wide margin ahead (the input
token's own embedding), so the gap is a coarse test.  ``score`` returns
every logit of the prompt.  At each prompt position the relative error
||served - reference|| / ||reference|| of its logits is read; the
second number compared is the configuration's ``score_err_quantile``
of these over the positions, in the worst sampled ``score`` request.
A quantile, and not the norm over all positions: a top-k routing
choice or a capacity drop that a rounding tips one way changes a
position's logits far more than the rounding itself, in the program
and in a lower precision alike, and the positions without such a flip
show the precision.

The control is the same forward with the linear layers' operands
rounded to a lower precision (``control`` in the configuration's
``check``): at the same positions, the gap of the token it puts first,
and its own logits' errors.  Beside it, the gap of the served tokens
each altered to the next id, as a decode step that alters its token
would serve them.
"""

from __future__ import annotations

import gc
from dataclasses import dataclass, field

import numpy as np

from harness import reference_module

COLD_FIRSTS = 4
MAX_REQUESTS = 12
TOKEN_BUDGET = 600


@dataclass
class Reading:
    widest_gap: float
    errs: list       # per sampled score request: its positions' errors
    tokens: int
    requests: int
    control_gap: float = float("nan")
    control_errs: list = field(default_factory=list)
    altered_gap: float = float("nan")


def score_err(errs: list, q: float) -> float:
    """The ``q``-quantile of the positions' relative errors in the worst
    request (nan with no ``score`` request)."""
    return max((float(np.quantile(e, q)) for e in errs),
               default=float("nan"))


def sample(records: list, seed: int) -> list:
    """The requests whose outputs are compared, drawn from ``seed``."""
    done = [r for r in records if r["path"] != "failed"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 300]))
    picked: dict[int, dict] = {}
    for r in done:
        if r["entry"] == "score":
            picked[r["i"]] = r
    gens = [r for r in done if r["entry"] == "generate"]
    if gens:
        longest = max(gens, key=lambda r: r["new_tokens"])
        picked[longest["i"]] = longest
    cold = [r for r in done if r["path"] == "cold"]
    for j in sorted(rng.permutation(len(cold))[:COLD_FIRSTS].tolist()):
        picked[cold[j]["i"]] = cold[j]
    decoded = sum(r["new_tokens"] for r in picked.values())
    for j in rng.permutation(len(done)):
        if decoded >= TOKEN_BUDGET or len(picked) >= MAX_REQUESTS:
            break
        r = done[int(j)]
        if r["i"] not in picked:
            picked[r["i"]] = r
            decoded += r["new_tokens"]
    return [picked[i] for i in sorted(picked)]


def _inputs(r, length: int):
    """(tokens, targets) of one request for a forward of ``length``
    positions: targets[p] is the served token that position p's logits
    chose, -1 where nothing was served."""
    prompt = np.asarray(r["prompt"], np.int32)
    P = len(prompt)
    toks = np.zeros(length, np.int32)
    tgt = np.full(length, -1, np.int32)
    if r["entry"] == "score":
        toks[:P] = prompt
        tgt[:P] = np.asarray(r["out"])[0].argmax(-1)
    else:
        out = np.asarray(r["out"])[0]
        seq = np.concatenate([prompt, out[:-1]])
        toks[:len(seq)] = seq
        tgt[P - 1:P - 1 + len(out)] = out
    return toks, tgt


def gap_program(mod, m, prompt_len: int, control=None):
    """Jitted (params, tokens, targets, served prompt logits) -> the
    widest gap of the served tokens and the relative error of the
    served logits at each prompt position; with ``control`` naming a
    lower precision, the same two readings of the control and the
    widest gap of the served tokens altered to the next id."""
    import jax
    import jax.numpy as jnp
    from reference.common import QUANT

    def pos_err(logits, ref):
        return jnp.linalg.norm(logits[:prompt_len] - ref[:prompt_len],
                               axis=-1) / \
            jnp.linalg.norm(ref[:prompt_len], axis=-1)

    def gaps(params, toks, tgt, served):
        ref = mod.forward(params, toks, m, prompt_len)
        best = ref.max(-1)

        def widest(tok):
            got = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
            return jnp.where(tgt >= 0, best - got, 0.0).max()

        out = {"gap": widest(jnp.maximum(tgt, 0)),
               "err": pos_err(served, ref)}
        if control:
            ctl = mod.forward(params, toks, m, prompt_len, QUANT[control])
            out["control_gap"] = widest(ctl.argmax(-1).astype(jnp.int32))
            out["control_err"] = pos_err(ctl, ref)
            altered = jnp.where(tgt >= 0, (tgt + 1) % ref.shape[-1], 0)
            out["altered_gap"] = widest(altered)
        return out

    return jax.jit(gaps)


def read(spec: dict, tenants: dict, picked: list, *,
         control: str | None = None) -> Reading:
    """Run the reference over ``picked`` (tenant name -> weight seed in
    ``tenants``) and return the widest gaps and the score requests'
    position errors."""
    import jax
    mod = reference_module(spec)
    eng = spec["engine"]
    fn = gap_program(mod, spec, eng["prefill_len"], control)
    import jax.numpy as jnp
    gap = {"gap": 0.0, "control_gap": 0.0, "altered_gap": 0.0}
    errs = {"err": [], "control_err": []}
    tokens = 0
    no_logits = jnp.zeros((eng["max_len"], spec["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        for tenant in sorted({r["tenant"] for r in picked}):
            params = mod.weights(spec, tenants[tenant])
            for r in (r for r in picked if r["tenant"] == tenant):
                toks, tgt = _inputs(r, eng["max_len"])
                score = r["entry"] == "score"
                served = _padded(r["out"], eng["max_len"]) if score \
                    else no_logits
                got = jax.device_get(fn(params, toks, tgt, served))
                for k in gap.keys() & got.keys():
                    gap[k] = max(gap[k], float(got[k]))
                for k in errs.keys() & got.keys():
                    if score:
                        errs[k].append(np.asarray(got[k], np.float64))
                tokens += int((tgt >= 0).sum())
            del params
            gc.collect()
    nan = float("nan")
    return Reading(gap["gap"], errs["err"], tokens, len(picked),
                   gap["control_gap"] if control else nan,
                   errs["control_err"],
                   gap["altered_gap"] if control else nan)


def _padded(logits, length: int):
    out = np.zeros((length,) + np.shape(logits)[2:], np.float32)
    out[:np.shape(logits)[1]] = np.asarray(logits)[0]
    return out


def control_in_place(reading: Reading) -> Reading:
    """The control read as if the program had served it: its own first
    choices and logits in place of the served ones."""
    return Reading(reading.control_gap, reading.control_errs,
                   reading.tokens, reading.requests)


def verdict(spec: dict, reading: Reading, failed: int) -> dict:
    """The numbers compared, each with its limit."""
    return {
        "logit_gap": {"value": reading.widest_gap,
                      "limit": spec["check"]["logit_gap_limit"]},
        "score_err": {"value": score_err(reading.errs,
                                         spec["check"]["score_err_quantile"]),
                      "limit": spec["check"]["score_err_limit"]},
        "failed_requests": {"value": failed, "limit": 0},
    }


def is_correct(compared: dict, reading: Reading) -> bool:
    """Every number within its limit, and something was compared."""
    return (reading.tokens > 0 and all(
        c["value"] <= c["limit"] for c in compared.values()))
