"""Multiple-Token Prediction (paper §4.2.4) with CPU-free in-graph sampling.

DeepSeek-style MTP: a lightweight draft module predicts one speculative token
per decode step; the next step validates it against the main model. The paper
identifies two pipeline-break sources — CPU-side metadata init and CPU-side
sampling — and removes both. Our JAX analogue is strictly stronger: the whole
iteration (draft, validation, acceptance, sampling, cache update) is a single
jitted graph. Metadata (sequence lengths) is precomputed as traced values
("aggregated metadata initialization") and sampling runs on-device as sort/
cumsum/filter ops fused into the step ("CPU-free in-NPU sampling").

Three modes:
* ``mtp_step``     — batched aligned MTP: every request processes base +
  speculative token per iteration; acceptance is per-request, emission is
  (1 + accepted) tokens. Cache stays aligned by re-validating from the base
  slot each iteration (rejected speculative entries are overwritten), exactly
  the paper's "varying effective sequence lengths within the same batch".
* ``fused_verify=True`` — the base and speculative tokens run through the
  main model in ONE two-token teacher-forced forward (``attention_extend`` /
  ``mla_extend`` with per-request offsets) instead of two sequential decode
  steps: one pass over the weights per iteration, the memory-bound regime
  where the paper's +44% iteration latency (Fig. 22b) comes from.
* ``model.decode_loop_mtp`` — N MTP iterations in one ``lax.scan`` (the
  device-resident serving fast path; see models/model.py).
* benchmarks model the paper's 70% single-token acceptance when comparing
  against SGLang "Simulated MTP" (paper Table 4); ``fit_draft_head``
  distills a smoke-scale draft head so live benches measure real acceptance.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as model_mod
from repro.models.layers import dense_init, rms_norm


def init_mtp_params(key, cfg: ModelConfig) -> dict:
    """Draft head: combine last hidden + next-token embedding -> logits.
    (DeepSeek MTP module distilled to one projection block.)"""
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    return {
        "ln": jnp.ones((d,), jnp.dtype(cfg.dtype)),
        "mix": dense_init(k1, (2 * d, d), jnp.dtype(cfg.dtype)),
        "proj": dense_init(k2, (d, d), jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# On-device sampling (paper: "CPU-Free In-NPU Sampling")
# ---------------------------------------------------------------------------


def sample_top_p(key, logits: jax.Array, temperature: float = 0.6,
                 top_p: float = 0.95) -> jax.Array:
    """Nucleus sampling entirely in-graph: sort -> cumsum -> filter -> gumbel.
    logits: (B, V) -> (B,) int32. Temperature/top-p default to the paper's
    DeepSeek-R1 eval settings (§5.3).

    The filter always keeps at least one token per row: the cutoff index is
    clamped to V-1 so ``top_p >= 1.0`` (every prefix mass can stay below
    top_p) selects the whole vocabulary instead of indexing out of bounds,
    and the ``>= cutoff`` comparison keeps the top token even when its mass
    alone exceeds ``top_p``."""
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    v = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # keep the smallest prefix with cumulative mass >= top_p (>= 1 token)
    cutoff_idx = jnp.minimum(jnp.sum(cum < top_p, axis=-1, keepdims=True),
                             v - 1)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
    filtered = jnp.where(logits >= cutoff, logits, -1e30)
    g = -jnp.log(-jnp.log(jax.random.uniform(key, filtered.shape) + 1e-20) + 1e-20)
    return jnp.argmax(filtered + g, axis=-1).astype(jnp.int32)


def sample_greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# MTP decode iteration
# ---------------------------------------------------------------------------


def draft_logits(params: dict, mtp: dict, cfg: ModelConfig,
                 hidden: jax.Array, next_tok: jax.Array) -> jax.Array:
    """hidden: (B, D) final hidden of base token; next_tok: (B,) sampled."""
    emb = params["embed"][next_tok].astype(hidden.dtype)
    h = jnp.concatenate([rms_norm(hidden, mtp["ln"], cfg.norm_eps), emb], axis=-1)
    h = jax.nn.silu(jnp.einsum("bd,de->be", h, mtp["mix"]))
    h = jnp.einsum("bd,de->be", h, mtp["proj"])
    return model_mod.unembed(params, cfg, h)


def propose_draft(params: dict, mtp: dict, cfg: ModelConfig,
                  token: jax.Array) -> jax.Array:
    """Draft the successor of ``token`` (B,) -> (B,)."""
    hidden = params["embed"][token].astype(jnp.dtype(cfg.dtype))
    return sample_greedy(draft_logits(params, mtp, cfg, hidden, token))


def can_fuse_verify(cfg: ModelConfig, capacity: int) -> bool:
    """Is the one-forward base+draft verification available? Requires a
    token-addressable, non-ring cache (the extend kernels' contract —
    exactly :func:`repro.models.model.supports_prefill_continue`)."""
    return model_mod.supports_prefill_continue(cfg, capacity)


def verify_pair(params: dict, cfg: ModelConfig, x_prev: jax.Array,
                d_prev: jax.Array, caches: Dict[str, Any],
                cache_len: jax.Array, moe_fn=None
                ) -> Tuple[jax.Array, jax.Array, Dict[str, Any]]:
    """Fused verification: run (x_prev, d_prev) at per-request positions
    (cache_len, cache_len+1) through the main model in ONE teacher-forced
    forward — one pass over the weights instead of two sequential decode
    steps. Returns (logits1 (B,V), logits2 (B,V), new caches); logits1
    scores the successor of x_prev, logits2 the successor of d_prev."""
    pair = jnp.stack([x_prev, d_prev], axis=1)              # (B, 2)
    logits, caches = model_mod.prefill_continue(params, cfg, pair, caches,
                                                cache_len, moe_fn)
    return logits[:, 0, :], logits[:, 1, :], caches


def mtp_step(params: dict, mtp: dict, cfg: ModelConfig,
             x_prev: jax.Array, d_prev: jax.Array,
             caches: Dict[str, Any], cache_len: jax.Array,
             key: jax.Array, moe_fn=None, greedy: bool = True,
             fused_verify: bool = False
             ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                        Dict[str, Any], jax.Array]:
    """One MTP iteration (k=1 speculative decode).

    Carry: ``x_prev`` (B,) — last committed token (its KV not yet cached) at
    per-request positions ``cache_len`` (B,), and ``d_prev`` (B,) — the draft
    of x_prev's successor proposed last iteration.

    The iteration runs BOTH tokens through the main model in one graph:

      f1 = decode(x_prev, len)   -> logits₁ ; slot len     = x_prev KV (always right)
      f2 = decode(d_prev, len+1) -> logits₂ ; slot len+1   = d_prev KV (speculative)
      y1 = sample(logits₁)                — the true token at len+1 (emitted)
      accepted = (y1 == d_prev)           — speculation validated
      y2 = sample(logits₂)                — token at len+2, valid iff accepted

    Accepted requests emit 2 tokens and advance 2; rejected requests emit 1,
    advance 1, and their stale slot len+1 is overwritten next iteration by
    the per-request scatter write (attention.write_row). This is exactly
    the paper's §4.2.2-(3) regime: effective sequence lengths diverge within
    one batch, handled by per-request (B,) cache_len masks. In the
    memory-bound decode regime the two forwards share one weight stream, so
    wall-clock/iter ≈ one forward while emitting 1+α tokens (paper: α≈0.7).

    No CPU in the loop: metadata (cache_len±1) is traced ("aggregated
    metadata initialization") and sampling is in-graph ("CPU-free in-NPU
    sampling"). With ``fused_verify`` both forwards collapse into one
    two-token teacher-forced pass (:func:`verify_pair`) — same token
    semantics, one weight stream per iteration (requires
    :func:`can_fuse_verify`; float reduction order differs from the
    two-step form, so it is not bitwise-identical to it). Returns
    (emitted (B,2), accepted (B,), x_next, d_next, caches, new_len).
    """
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, x_prev.shape[:1])
    k1, k2 = jax.random.split(key)
    if fused_verify:
        logits1, logits2, caches = verify_pair(params, cfg, x_prev, d_prev,
                                               caches, cache_len, moe_fn)
    else:
        logits1, caches = model_mod.decode_step(params, cfg, x_prev[:, None],
                                                caches, cache_len, moe_fn)
        logits2, caches = model_mod.decode_step(params, cfg, d_prev[:, None],
                                                caches, cache_len + 1, moe_fn)
    y1 = sample_greedy(logits1) if greedy else sample_top_p(k1, logits1)
    accepted = y1 == d_prev
    y2 = sample_greedy(logits2) if greedy else sample_top_p(k2, logits2)
    emitted = jnp.stack([y1, y2], axis=1)
    x_next = jnp.where(accepted, y2, y1)
    d_next = propose_draft(params, mtp, cfg, x_next)
    new_len = cache_len + 1 + accepted.astype(jnp.int32)
    return emitted, accepted, x_next, d_next, caches, new_len


# ---------------------------------------------------------------------------
# Draft-head distillation (smoke-scale stand-in for the trained MTP module)
# ---------------------------------------------------------------------------


def fit_draft_head(params: dict, cfg: ModelConfig, mtp: dict, key: jax.Array,
                   *, prompts: Optional[jax.Array] = None, n_seq: int = 16,
                   prompt_len: int = 12, gen_len: int = 32, steps: int = 300,
                   lr: float = 3e-3, moe_fn=None) -> dict:
    """Distill the draft head against the base model's own greedy
    continuations of ``prompts`` (random prompts when omitted).

    Real deployments ship an MTP module trained jointly with the base model
    (paper α≈0.7); our smoke models are random, so an untrained head accepts
    at chance level and every MTP measurement degenerates. This fits the
    head's (token -> successor) map on self-generated traces with plain
    in-repo Adam, so measured acceptance reflects the mechanism rather than
    draft quality. A random base model's successor map is context-specific
    — there is nothing for a one-token head to generalize to — so pass the
    *serving* prompt distribution for meaningful live-bench acceptance
    (the trained-MTP analogue of matching train and serve distributions).

    Returns the updated draft-head params (base ``params`` stay frozen).
    """
    if prompts is None:
        k_prompt, _ = jax.random.split(key)
        prompts = jax.random.randint(k_prompt, (n_seq, prompt_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
    prompts = jnp.asarray(prompts, jnp.int32)
    n_seq, prompt_len = prompts.shape
    capacity = prompt_len + gen_len + 2
    logits, caches = model_mod.prefill(params, cfg, {"tokens": prompts},
                                       capacity, moe_fn,
                                       cache_dtype=jnp.float32)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    cl0 = jnp.full((n_seq,), prompt_len, jnp.int32)
    em = model_mod.decode_loop(params, cfg, tok0, caches, cl0, gen_len,
                               moe_fn=moe_fn)[0]
    seq = jnp.concatenate([tok0[:, None], em], axis=1)       # (n_seq, G+1)
    cur = seq[:, :-1].reshape(-1)
    nxt = seq[:, 1:].reshape(-1)

    def loss_fn(mp):
        hidden = params["embed"][cur].astype(jnp.dtype(cfg.dtype))
        lg = draft_logits(params, mp, cfg, hidden, cur).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    @jax.jit
    def adam_step(mp, mu, nu, t):
        g = jax.grad(loss_fn)(mp)
        mu = jax.tree.map(lambda m, gg: 0.9 * m + 0.1 * gg, mu, g)
        nu = jax.tree.map(lambda v, gg: 0.999 * v + 0.001 * gg * gg, nu, g)
        mp = jax.tree.map(
            lambda p, m, v: (p - lr * (m / (1 - 0.9 ** t))
                             / (jnp.sqrt(v / (1 - 0.999 ** t)) + 1e-8)
                             ).astype(p.dtype),
            mp, mu, nu)
        return mp, mu, nu

    mu = jax.tree.map(jnp.zeros_like, mtp)
    nu = jax.tree.map(jnp.zeros_like, mtp)
    for t in range(1, steps + 1):
        mtp, mu, nu = adam_step(mtp, mu, nu, jnp.float32(t))
    return mtp
