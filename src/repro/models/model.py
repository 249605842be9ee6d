"""Unified transformer assembly for every assigned architecture family.

The model is organized as *segments* of structurally-identical layers; each
segment's parameters are stacked on a leading layer axis and executed with
``jax.lax.scan`` (keeps 512-device dry-run compiles tractable and HLO small).

Families → segment plans:
  dense / vlm / audio : [dense × L]
  moe                 : [dense × first_k_dense] + [moe × (L - k)]
  ssm                 : [mamba × L]
  hybrid (zamba2)     : [mamba groups of ``attn_every`` + one *shared* attention
                         block applied after each group] + [mamba tail]

Six entry points: ``forward`` (full-sequence, training), ``prefill``
(full-sequence + cache materialization), ``decode_step`` (one token),
``decode_loop`` (N scanned decode steps with on-device greedy sampling —
the serving fast path), ``decode_loop_mtp`` (N scanned MTP speculative
iterations with on-device accept/reject — up to 2N tokens per host sync),
and ``prefill_continue`` (teacher-forced continuation against an existing
cache: the EMS-reuse suffix path, the bounded-shape fresh-prefill chunk
step, and — with per-request offsets — the MTP fused verification
forward).
MoE execution is pluggable via ``moe_fn`` — default is the single-device
capacity implementation; ``core/lep.py`` supplies the shard_map LEP version.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


from repro.models.scan_util import scan_unroll  # noqa: E402


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=scan_unroll())

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.attention import KVCache
from repro.models.layers import dense_init, rms_norm, swiglu
from repro.models.mamba2 import SSMState

MoeFn = Callable[[dict, jax.Array, ModelConfig], Tuple[jax.Array, Dict[str, jax.Array]]]


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str        # dense | moe | mamba_groups | mamba_tail
    n_layers: int    # layers in this segment (groups*per_group for mamba_groups)
    per_group: int = 0


def build_plan(cfg: ModelConfig) -> List[Segment]:
    if cfg.is_hybrid:
        groups = cfg.num_layers // cfg.attn_every
        tail = cfg.num_layers % cfg.attn_every
        plan = [Segment("mamba_groups", "mamba_groups",
                        groups * cfg.attn_every, cfg.attn_every)]
        if tail:
            plan.append(Segment("mamba_tail", "mamba_tail", tail))
        return plan
    if cfg.is_ssm:
        return [Segment("mamba", "mamba_tail", cfg.num_layers)]
    if cfg.is_moe:
        plan = []
        if cfg.first_k_dense:
            plan.append(Segment("dense_lead", "dense", cfg.first_k_dense))
        plan.append(Segment("moe", "moe", cfg.num_layers - cfg.first_k_dense))
        return plan
    return [Segment("dense", "dense", cfg.num_layers)]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig, n_layers: int, dtype):
    if cfg.attention_kind == "mla":
        return mla_mod.init_mla_params(key, cfg, n_layers, dtype)
    return attn_mod.init_attention_params(key, cfg, n_layers, dtype)


def _init_mlp(key, cfg: ModelConfig, n_layers: int, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.ones((n_layers, d), dtype),
        "w_gate": dense_init(ks[0], (n_layers, d, f), dtype),
        "w_up": dense_init(ks[1], (n_layers, d, f), dtype),
        "w_down": dense_init(ks[2], (n_layers, f, d), dtype),
    }


def init_params(key, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg)
    plan = build_plan(cfg)
    keys = jax.random.split(key, len(plan) + 4)
    params: dict = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), dtype, scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "segments": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size), dtype)
    for i, seg in enumerate(plan):
        k = keys[2 + i]
        if seg.kind == "dense":
            ka, km = jax.random.split(k)
            params["segments"][seg.name] = {
                "attn": _init_attn(ka, cfg, seg.n_layers, dtype),
                "mlp": _init_mlp(km, cfg, seg.n_layers, dtype),
            }
        elif seg.kind == "moe":
            ka, km = jax.random.split(k)
            params["segments"][seg.name] = {
                "attn": _init_attn(ka, cfg, seg.n_layers, dtype),
                "moe": moe_mod.init_moe_params(km, cfg, seg.n_layers, dtype),
            }
        else:  # mamba_groups / mamba_tail
            params["segments"][seg.name] = {
                "mamba": mamba_mod.init_mamba_params(k, cfg, seg.n_layers, dtype),
            }
    if cfg.is_hybrid:
        ka, km = jax.random.split(keys[-1])
        params["shared_attn"] = {
            "attn": _init_attn(ka, cfg, 1, dtype),
            "mlp": _init_mlp(km, cfg, 1, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_inputs(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    if cfg.frontend == "audio_frames":
        return batch["frames"].astype(_dtype(cfg))
    x = params["embed"][batch["tokens"]]
    if cfg.frontend == "vision_patches" and "prefix_emb" in batch:
        x = jnp.concatenate([batch["prefix_emb"].astype(x.dtype), x], axis=1)
    return x


def unembed(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...d,dv->...v", x, head)


# ---------------------------------------------------------------------------
# Per-layer blocks (single-layer params)
# ---------------------------------------------------------------------------


# Each block runs under a named scope ("attention", "mlp", "moe"): the
# scope lands in the ops' metadata only, so a device trace can group the
# operations of one layer part; the computation is unchanged.


@jax.named_scope("attention")
def _attn_block_prefill(pl_attn, x, cfg, positions):
    h = rms_norm(x, pl_attn["ln"], cfg.norm_eps)
    if cfg.attention_kind == "mla":
        mode = os.environ.get("REPRO_MLA_HYBRID", "")
        if mode in ("a2a", "rs"):
            # Paper §4.3.1 staged hybrid parallelism (SP→TP→SP) — enabled
            # for prefill when a mesh context is active (launch/variants).
            from repro.core.parallel import get_current_mesh
            mesh = get_current_mesh()
            if mesh is not None:
                from repro.core.hybrid_parallel import mla_prefill_hybrid
                out, latent = mla_prefill_hybrid(pl_attn, h, cfg, mesh,
                                                 oproj_mode=mode)
                return x + out, latent
        out, latent = mla_mod.mla_prefill(pl_attn, h, cfg, positions)
        return x + out, latent
    out, (k, v) = attn_mod.attention_prefill(pl_attn, h, cfg, positions)
    return x + out, (k, v)


@jax.named_scope("attention")
def _attn_block_decode(pl_attn, x, cfg, cache_k, cache_v, cache_len, ring,
                       layer, write_pos):
    """Layer ``layer`` of a decode step against the segment's stacked
    caches (MLA: the latents in ``cache_k``, ``cache_v`` None), each written
    one row in place at ``write_pos``."""
    h = rms_norm(x, pl_attn["ln"], cfg.norm_eps)
    if cfg.attention_kind == "mla":
        out, new_cache = mla_mod.mla_decode(pl_attn, h, cache_k, cache_len,
                                            cfg, layer, write_pos)
        return x + out, new_cache, None
    out, ck, cv = attn_mod.attention_decode(pl_attn, h, cache_k, cache_v,
                                            cache_len, cfg, ring, layer,
                                            write_pos)
    return x + out, ck, cv


@jax.named_scope("mlp")
def _mlp_block(pl_mlp, x, cfg):
    h = rms_norm(x, pl_mlp["ln"], cfg.norm_eps)
    return x + swiglu(h, pl_mlp["w_gate"], pl_mlp["w_up"], pl_mlp["w_down"])


@jax.named_scope("moe")
def _moe_block(pl_moe, x, cfg, moe_fn: MoeFn):
    b, s, d = x.shape
    h = rms_norm(x, pl_moe["ln"], cfg.norm_eps)
    out, aux = moe_fn(pl_moe, h.reshape(b * s, d), cfg)
    return x + out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Full-sequence execution (training / prefill)
# ---------------------------------------------------------------------------


def _seg_full(seg: Segment, seg_params: dict, shared_attn, x, cfg: ModelConfig,
              moe_fn: MoeFn, positions, want_cache: bool):
    """Run a segment over the full sequence via lax.scan over layers."""
    aux0 = jnp.zeros((), jnp.float32)

    if seg.kind in ("dense", "moe"):
        def body(carry, pl):
            h, aux = carry
            h, cache = _attn_block_prefill(pl["attn"], h, cfg, positions)
            if seg.kind == "moe":
                h, a = _moe_block(pl["moe"], h, cfg, moe_fn)
                aux = aux + a["aux_loss"]
            else:
                h = _mlp_block(pl["mlp"], h, cfg)
            ys = cache if want_cache else None
            return (h, aux), ys

        (x, aux), caches = _scan(body, (x, aux0), seg_params)
        return x, aux, caches

    if seg.kind == "mamba_tail":
        def body(carry, pl):
            h, aux = carry
            hin = rms_norm(h, pl["mamba"]["ln"], cfg.norm_eps)
            out, hstate, conv = mamba_mod.mamba_prefill(pl["mamba"], hin, cfg)
            ys = (hstate, conv) if want_cache else None
            return (h + out, aux), ys

        (x, aux), caches = _scan(body, (x, aux0), seg_params)
        return x, aux, caches

    # mamba_groups: scan over groups; each group = per_group mamba layers
    # (inner scan) followed by the *shared* attention block (closure params).
    g = seg.n_layers // seg.per_group
    grouped = jax.tree.map(
        lambda a: a.reshape((g, seg.per_group) + a.shape[1:]), seg_params)

    def group_body(carry, pl_group):
        h, aux = carry

        def inner(hc, pl):
            hin = rms_norm(hc, pl["mamba"]["ln"], cfg.norm_eps)
            out, hstate, conv = mamba_mod.mamba_prefill(pl["mamba"], hin, cfg)
            return hc + out, (hstate, conv) if want_cache else None

        h, mcaches = _scan(inner, h, pl_group)
        pl_sa = jax.tree.map(lambda a: a[0], shared_attn)
        h, kv = _attn_block_prefill(pl_sa["attn"], h, cfg, positions)
        h = _mlp_block(pl_sa["mlp"], h, cfg)
        ys = (mcaches, kv) if want_cache else None
        return (h, aux), ys

    (x, aux), caches = _scan(group_body, (x, aux0), grouped)
    return x, aux, caches


def forward(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
            moe_fn: Optional[MoeFn] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward (no cache). Returns (logits, aux)."""
    moe_fn = moe_fn or moe_mod.moe_capacity
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    aux_total = jnp.zeros((), jnp.float32)
    for seg in build_plan(cfg):
        x, aux, _ = _seg_full(seg, params["segments"][seg.name],
                              params.get("shared_attn"), x, cfg, moe_fn,
                              positions, want_cache=False)
        aux_total = aux_total + aux
    logits = unembed(params, cfg, x)
    return logits, {"aux_loss": aux_total}


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def make_caches(cfg: ModelConfig, batch: int, capacity: int,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    caches: Dict[str, Any] = {}
    for seg in build_plan(cfg):
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                caches[seg.name] = {
                    "mla": mla_mod.make_mla_cache(cfg, seg.n_layers, batch, capacity, dtype),
                    "length": jnp.zeros((), jnp.int32),
                }
            else:
                cap = cfg.sliding_window if attn_mod.is_ring(cfg, capacity) else capacity
                kvshape = (seg.n_layers, batch, cap, cfg.num_kv_heads, cfg.head_dim)
                caches[seg.name] = KVCache(jnp.zeros(kvshape, dtype),
                                           jnp.zeros(kvshape, dtype),
                                           jnp.zeros((), jnp.int32))
        elif seg.kind == "mamba_tail":
            caches[seg.name] = mamba_mod.make_ssm_state(cfg, seg.n_layers, batch)
        else:  # mamba_groups
            g = seg.n_layers // seg.per_group
            din = cfg.d_model * cfg.ssm_expand
            conv_ch = din + 2 * cfg.ssm_state
            caches[seg.name] = {
                "ssm": {
                    "h": jnp.zeros((g, seg.per_group, batch, cfg.ssm_heads,
                                    cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
                    "conv": jnp.zeros((g, seg.per_group, batch,
                                       cfg.ssm_conv - 1, conv_ch), jnp.bfloat16),
                    "length": jnp.zeros((), jnp.int32),
                },
                "length": jnp.zeros((), jnp.int32),
            }
            cap = cfg.sliding_window if attn_mod.is_ring(cfg, capacity) else capacity
            kvshape = (g, batch, cap, cfg.num_kv_heads, cfg.head_dim)
            caches[seg.name]["shared_kv"] = KVCache(
                jnp.zeros(kvshape, dtype), jnp.zeros(kvshape, dtype),
                jnp.zeros((), jnp.int32))
    return caches


# ---------------------------------------------------------------------------
# Decode step (one new token per request)
# ---------------------------------------------------------------------------


def _held_rows(aux: Dict[str, jax.Array], b: int) -> jax.Array:
    """Per request, a MoE block's routings onto the experts held here
    (``moe_capacity``'s ``aux["held"]``; zeros where the MoE fn has none)."""
    held = aux.get("held")
    if held is None:
        return jnp.zeros((b,), jnp.int32)
    return jnp.sum(held.reshape(b, -1), axis=1, dtype=jnp.int32)


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                caches: Dict[str, Any], cache_len: jax.Array,
                moe_fn: Optional[MoeFn] = None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: (B, 1) int32. Returns (logits (B, V), updated caches)."""
    (logits, _), new_caches = decode_step_held(params, cfg, tokens, caches,
                                               cache_len, moe_fn)
    return logits, new_caches


def _ring_cache(cfg: ModelConfig, cap: int) -> bool:
    """A sequence buffer of ``sliding_window`` tokens is written as a ring."""
    return bool(cfg.sliding_window) and cap == cfg.sliding_window


def decode_step_held(params: dict, cfg: ModelConfig, tokens: jax.Array,
                     caches: Dict[str, Any], cache_len: jax.Array,
                     moe_fn: Optional[MoeFn] = None,
                     live: Optional[jax.Array] = None
                     ) -> Tuple[Tuple[jax.Array, jax.Array], Dict[str, Any]]:
    """:func:`decode_step` that also counts, per request, the routings onto
    held experts over the MoE layers: ((logits (B, V), held (B,)), caches);
    ``held`` is zero for a model without experts. The step
    :func:`decode_loop` scans.

    Sequence caches (KV, MLA latents, the hybrid's shared KV) ride the
    layer scan's carry whole, and each layer writes one row per request
    into them in place. A request not ``live`` (B,) bool, default all live,
    keeps every cache leaf bit for bit: its rows go to the capacity index,
    where the write is dropped; the SSM states, which a step rewrites whole,
    keep their old value by a select. RoPE positions and the attention mask
    read ``cache_len`` either way, so a live request computes the same."""
    moe_fn = moe_fn or moe_mod.moe_capacity
    x = params["embed"][tokens].astype(_dtype(cfg))           # (B,1,D)
    b = x.shape[0]
    held = jnp.zeros((b,), jnp.int32)

    def freeze(new, old, ax):
        return new if live is None else _masked_select(live, new, old, ax, b)

    new_caches: Dict[str, Any] = {}
    for seg in build_plan(cfg):
        seg_params = params["segments"][seg.name]
        cache = caches[seg.name]
        if seg.kind in ("dense", "moe"):
            mla = cfg.attention_kind == "mla"
            ck, cv = (cache["mla"], None) if mla else (cache.k, cache.v)
            cap = ck.shape[2]
            ring = not mla and _ring_cache(cfg, cap)
            pos = attn_mod.decode_write_pos(cache_len, cap, ring, live)

            def body(carry, xs):
                h, n, ck, cv = carry
                pl, layer = xs
                h2, ck, cv = _attn_block_decode(pl["attn"], h, cfg, ck, cv,
                                                cache_len, ring, layer, pos)
                if seg.kind == "moe":
                    h2, aux = _moe_block(pl["moe"], h2, cfg, moe_fn)
                    n = n + _held_rows(aux, b)
                else:
                    h2 = _mlp_block(pl["mlp"], h2, cfg)
                return (h2, n, ck, cv), None

            (x, held, ck, cv), _ = _scan(
                body, (x, held, ck, cv),
                (seg_params, jnp.arange(seg.n_layers, dtype=jnp.int32)))
            new_caches[seg.name] = ({"mla": ck, "length": cache_len + 1} if mla
                                    else KVCache(ck, cv, cache_len + 1))
        elif seg.kind == "mamba_tail":
            def body(h, xs):
                pl, hs, cs = xs
                hin = rms_norm(h, pl["mamba"]["ln"], cfg.norm_eps)
                out, nhs, ncs = mamba_mod.mamba_decode(pl["mamba"], hin, hs, cs, cfg)
                return h + out, (nhs, ncs)

            x, (nh, nc) = _scan(body, x, (seg_params, cache.h, cache.conv))
            new_caches[seg.name] = SSMState(freeze(nh, cache.h, 1),
                                            freeze(nc, cache.conv, 1),
                                            cache_len + 1)
        else:  # mamba_groups
            g = seg.n_layers // seg.per_group
            grouped = jax.tree.map(
                lambda a: a.reshape((g, seg.per_group) + a.shape[1:]), seg_params)
            kv = cache["shared_kv"]
            cap = kv.k.shape[2]
            ring = _ring_cache(cfg, cap)
            pos = attn_mod.decode_write_pos(cache_len, cap, ring, live)

            def group_body(carry, xs):
                h, ck, cv = carry
                pl_group, hs, cs, group = xs

                def inner(hc, ys):
                    pl, hs1, cs1 = ys
                    hin = rms_norm(hc, pl["mamba"]["ln"], cfg.norm_eps)
                    out, nhs, ncs = mamba_mod.mamba_decode(pl["mamba"], hin, hs1, cs1, cfg)
                    return hc + out, (nhs, ncs)

                h, (nhs, ncs) = _scan(inner, h, (pl_group, hs, cs))
                pl_sa = jax.tree.map(lambda a: a[0], params["shared_attn"])
                h, ck, cv = _attn_block_decode(pl_sa["attn"], h, cfg, ck, cv,
                                               cache_len, ring, group, pos)
                h = _mlp_block(pl_sa["mlp"], h, cfg)
                return (h, ck, cv), (nhs, ncs)

            ssm = cache["ssm"]
            (x, nk, nv), (nhs, ncs) = _scan(
                group_body, (x, kv.k, kv.v),
                (grouped, ssm["h"], ssm["conv"],
                 jnp.arange(g, dtype=jnp.int32)))
            new_caches[seg.name] = {
                "ssm": {"h": freeze(nhs, ssm["h"], 2),
                        "conv": freeze(ncs, ssm["conv"], 2),
                        "length": ssm["length"] + 1},
                "length": cache_len + 1,
                "shared_kv": KVCache(nk, nv, cache_len + 1),
            }
    logits = unembed(params, cfg, x[:, 0:1, :])[:, 0, :]
    return (logits, held), new_caches


# ---------------------------------------------------------------------------
# Cache pytree structure helpers (shared with serving/cache_ops.py)
# ---------------------------------------------------------------------------


def cache_batch_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Pytree of batch-axis indices matching the make_caches structure
    (None = unbatched leaf, e.g. length scalars)."""
    axes: Dict[str, Any] = {}
    for seg in build_plan(cfg):
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                axes[seg.name] = {"mla": 1, "length": None}
            else:
                axes[seg.name] = KVCache(1, 1, None)
        elif seg.kind == "mamba_tail":
            axes[seg.name] = SSMState(1, 1, None)
        else:
            axes[seg.name] = {
                "ssm": {"h": 2, "conv": 2, "length": None},
                "length": None,
                "shared_kv": KVCache(1, 1, None),
            }
    return axes


def _with_lengths(cfg: ModelConfig, caches: Dict[str, Any],
                  length: jax.Array) -> Dict[str, Any]:
    """Return caches with every bookkeeping ``length`` leaf set to ``length``
    (decode_loop carries per-slot lengths, so the leaves must keep a stable
    (B,) shape across scan iterations)."""
    out = dict(caches)
    for seg in build_plan(cfg):
        c = out[seg.name]
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                out[seg.name] = {**c, "length": length}
            else:
                out[seg.name] = KVCache(c.k, c.v, length)
        elif seg.kind == "mamba_tail":
            out[seg.name] = SSMState(c.h, c.conv, length)
        else:
            out[seg.name] = {
                **c,
                "ssm": {**c["ssm"], "length": length},
                "length": length,
                "shared_kv": KVCache(c["shared_kv"].k, c["shared_kv"].v,
                                     length),
            }
    return out


def _cache_capacity(cfg: ModelConfig, caches: Dict[str, Any]) -> Optional[int]:
    """Static token capacity of the tightest non-ring sequence buffer
    (None when nothing bounds decode length, e.g. pure-SSM or all-ring)."""
    caps = []
    for seg in build_plan(cfg):
        c = caches[seg.name]
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                caps.append(c["mla"].shape[2])
            elif not _ring_cache(cfg, c.k.shape[2]):
                caps.append(c.k.shape[2])
        elif seg.kind == "mamba_groups":
            cap = c["shared_kv"].k.shape[2]
            if not _ring_cache(cfg, cap):
                caps.append(cap)
    return min(caps) if caps else None


def decode_ready_caches(params: dict, cfg: ModelConfig,
                        caches: Dict[str, Any], cache_len: jax.Array,
                        moe_fn: Optional[MoeFn] = None,
                        step_fn: Optional[Callable] = None) -> Dict[str, Any]:
    """Normalize a fresh cache pytree to decode's shape/dtype fixed point:
    per-slot ``length`` leaves and post-step state dtypes (e.g. the hybrid
    conv window, bf16 after prefill -> f32 after one step; the upcast is
    exact). Keeps ``lax.scan`` carries stable and lets donated cache
    buffers alias input->output from the very first jitted step.
    ``step_fn`` has :func:`decode_loop`'s step contract."""
    b = cache_len.shape[0]
    if step_fn is None:
        def step_fn(t, c, l, live):
            return decode_step_held(params, cfg, t, c, l, moe_fn, live)
    caches = _with_lengths(cfg, caches, cache_len)
    tok = jnp.zeros((b, 1), jnp.int32)
    live = jnp.ones((b,), bool)
    for _ in range(2):
        try:
            out = jax.eval_shape(step_fn, tok, caches, cache_len, live)[1]
        except Exception:       # exotic step_fn: skip dtype stabilization
            break
        if all(c.dtype == o.dtype for c, o in
               zip(jax.tree.leaves(caches), jax.tree.leaves(out))):
            break
        caches = jax.tree.map(
            lambda c, o: c if c.dtype == o.dtype else c.astype(o.dtype),
            caches, out)
    return caches


# ---------------------------------------------------------------------------
# Scanned multi-step decode (device-resident fast path)
# ---------------------------------------------------------------------------


def _masked_select(mask: jax.Array, new: jax.Array, old: jax.Array,
                   ax, b: int) -> jax.Array:
    """Per-slot freeze: keep ``old`` where ``mask`` is False along the batch
    axis ``ax`` (None = unbatched bookkeeping leaf, always take ``new``)."""
    if ax is None:
        return new
    shape = [1] * new.ndim
    shape[ax] = b
    return jnp.where(mask.reshape(shape), new, old)


def decode_loop(params: dict, cfg: ModelConfig, tokens: jax.Array,
                caches: Dict[str, Any], cache_len: jax.Array, n_steps: int,
                *, steps_left: Optional[jax.Array] = None,
                moe_fn: Optional[MoeFn] = None,
                step_fn: Optional[Callable] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                           jax.Array, Dict[str, Any], jax.Array]:
    """``n_steps`` greedy decode iterations in one ``lax.scan`` — N tokens
    per host sync instead of one.

    Sampling (argmax) happens on-device, and per-slot done/capacity masking
    keeps finished or capacity-full slots frozen: their token, cache content,
    and ``cache_len`` hold bit-exactly while live slots advance, so a chunked
    engine emits token-identical output to ``n_steps`` sequential
    :func:`decode_step` calls. The step gets the ``live`` mask and drops a
    frozen slot's cache writes itself (:func:`decode_step_held`), so no
    iteration copies a whole cache.

    tokens: (B,) int32 current token per slot; cache_len: (B,) int32 (scalars
    are broadcast). steps_left: (B,) int32 tokens each slot still wants
    (defaults to ``n_steps`` everywhere; may exceed ``n_steps`` — the
    decode engine jits this function at several scan widths
    and dispatches the widest pre-jitted width that fits
    ``min(steps_left)``, so a slot's remaining budget routinely spans
    multiple dispatches). ``step_fn`` overrides the inner
    ``(tokens (B,1), caches, cache_len, live (B,)) -> ((logits, held),
    caches)`` step (:func:`decode_step_held`), which must leave a slot not
    ``live`` as it was — the hook the microbatch interleaver wraps.

    Returns ``(emitted (B, n_steps), live (B, n_steps), finite (B, n_steps),
    held (B, n_steps), tokens (B,), caches, cache_len)``; ``emitted[:, j]``
    is meaningful only where ``live[:, j]``; ``finite[:, j]`` says step j's
    logits row had no NaN or Inf; ``held[:, j]`` counts a live slot's
    routings onto held experts over the MoE layers at step j (0 where the
    slot is not live).
    Chunk-split invariance: because frozen slots hold bit-exactly (their
    writes are dropped) and live slots see the identical per-step
    computation, any partition of N total iterations into scan dispatches
    emits identical tokens.
    """
    if tokens.ndim != 1:
        raise ValueError(f"decode_loop wants tokens of shape (B,), "
                         f"got {tokens.shape}")
    if n_steps < 1:
        raise ValueError(f"decode_loop needs n_steps >= 1, got {n_steps}")
    b = tokens.shape[0]
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    if steps_left is None:
        steps_left = jnp.full((b,), n_steps, jnp.int32)
    else:
        # A stale/negative budget must read as "done", not wrap around.
        steps_left = jnp.maximum(jnp.asarray(steps_left, jnp.int32), 0)
    if step_fn is None:
        mf = moe_fn

        def step_fn(t, c, l, live):  # noqa: E731 — default inner step
            return decode_step_held(params, cfg, t, c, l, mf, live)

    cap = _cache_capacity(cfg, caches)
    caches = decode_ready_caches(params, cfg, caches, cache_len,
                                 step_fn=step_fn)

    def body(carry, _):
        tok, cl, left, cs = carry
        live = left > 0
        if cap is not None:
            live &= cl < cap
        (logits, held), ncs = step_fn(tok[:, None], cs, cl, live)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = jnp.where(live, nxt, tok)
        cl = cl + live.astype(jnp.int32)
        left = left - live.astype(jnp.int32)
        ncs = _with_lengths(cfg, ncs, cl)
        fin = jnp.isfinite(logits).all(axis=-1)
        return (tok, cl, left, ncs), (nxt, live, fin, jnp.where(live, held, 0))

    (tokens, cache_len, _, caches), (em, lv, fin, held) = jax.lax.scan(
        body, (tokens, cache_len, steps_left, caches), None, length=n_steps)
    return em.T, lv.T, fin.T, held.T, tokens, caches, cache_len


# ---------------------------------------------------------------------------
# Scanned MTP speculative decode (device-resident fast path, paper §4.2.4)
# ---------------------------------------------------------------------------


def decode_loop_mtp(params: dict, mtp: dict, cfg: ModelConfig,
                    tokens: jax.Array, drafts: jax.Array,
                    caches: Dict[str, Any], cache_len: jax.Array,
                    n_iters: int, *,
                    steps_left: Optional[jax.Array] = None,
                    key: Optional[jax.Array] = None,
                    greedy: bool = True, fused_verify: bool = False,
                    moe_fn: Optional[MoeFn] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                               jax.Array, Dict[str, Any], jax.Array]:
    """``n_iters`` MTP iterations in one ``lax.scan`` — up to ``2*n_iters``
    tokens per host sync with speculation, sampling, accept/reject, and
    cache bookkeeping all on-device (the §4.2.4 decode headline composed
    with the PR 2 chunked-decode fast path).

    Each iteration runs one :func:`repro.core.mtp.mtp_step`: base + draft
    verification forwards (or ONE fused two-token teacher-forced forward
    when ``fused_verify`` — see :func:`repro.core.mtp.can_fuse_verify`),
    in-graph sampling, per-slot accept/reject, and the next draft proposal.
    Accepted iterations advance ``cache_len`` by 2, rejected by 1 (the
    stale speculative KV slot is overwritten by the next live iteration's
    base write), so effective sequence lengths diverge within the batch.

    Per-slot masking composes with the chunked-decode rules: a slot is live
    while it still wants tokens (``steps_left > 0``) and both KV writes fit
    (``cache_len + 2 <= capacity``); frozen slots hold their token, draft,
    cache content, and ``cache_len`` bit-exactly.

    tokens/drafts: (B,) int32 — last committed token and its proposed
    successor (:func:`repro.core.mtp.propose_draft`). steps_left: (B,)
    tokens each slot still wants (defaults to ``2*n_iters``; may exceed
    what ``n_iters`` can drain — the decode engine dispatches
    several pre-jitted widths against the same remaining budgets, and
    greedy accept/reject is PRNG-independent so any width split commits
    identical tokens). Returns
    ``(emitted (B, n_iters, 2), accepted (B, n_iters), live (B, n_iters),
    tokens, drafts, caches, cache_len)``; row ``emitted[:, j]`` is
    meaningful only where ``live[:, j]``, and ``emitted[:, j, 1]`` only
    where additionally ``accepted[:, j]``.
    """
    from repro.core import mtp as mtp_mod  # deferred: core.mtp imports us

    if tokens.ndim != 1:
        raise ValueError(f"decode_loop_mtp wants tokens of shape (B,), "
                         f"got {tokens.shape}")
    if n_iters < 1:
        raise ValueError(f"decode_loop_mtp needs n_iters >= 1, got {n_iters}")
    b = tokens.shape[0]
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    if steps_left is None:
        steps_left = jnp.full((b,), 2 * n_iters, jnp.int32)
    else:
        steps_left = jnp.maximum(jnp.asarray(steps_left, jnp.int32), 0)
    if key is None:
        key = jax.random.PRNGKey(0)

    cap = _cache_capacity(cfg, caches)
    axes = cache_batch_axes(cfg)
    caches = decode_ready_caches(params, cfg, caches, cache_len, moe_fn)

    def body(carry, _):
        tok, drf, cl, left, k, cs = carry
        live = left > 0
        if cap is not None:
            live &= cl + 2 <= cap       # base + speculative writes must fit
        k, sub = jax.random.split(k)
        em, acc, x_next, d_next, ncs, new_len = mtp_mod.mtp_step(
            params, mtp, cfg, tok, drf, cs, cl, sub, moe_fn, greedy,
            fused_verify)
        acc &= live
        tok = jnp.where(live, x_next, tok)
        drf = jnp.where(live, d_next, drf)
        cl = jnp.where(live, new_len, cl)
        left = left - jnp.where(live, 1 + acc.astype(jnp.int32), 0)
        ncs = jax.tree.map(
            lambda n, o, ax: _masked_select(live, n, o, ax, b), ncs, cs, axes)
        ncs = _with_lengths(cfg, ncs, cl)
        return (tok, drf, cl, left, k, ncs), (em, acc, live)

    (tokens, drafts, cache_len, _, _, caches), (em, acc, lv) = jax.lax.scan(
        body, (tokens, drafts, cache_len, steps_left, key, caches), None,
        length=n_iters)
    return (jnp.moveaxis(em, 0, 1), acc.T, lv.T, tokens, drafts, caches,
            cache_len)


# ---------------------------------------------------------------------------
# Chunked suffix prefill (teacher-forced continuation, EMS-reuse fast path)
# ---------------------------------------------------------------------------


def supports_prefill_continue(cfg: ModelConfig, capacity: int) -> bool:
    """Static eligibility for :func:`prefill_continue` (and everything
    built on it: chunked suffix/fresh prefill, the MTP fused verification):
    a token-addressable, non-ring cache."""
    return (cfg.attention_kind in ("causal", "mla")
            and not cfg.is_ssm and not cfg.is_hybrid
            and not attn_mod.is_ring(cfg, capacity))


def prefill_continue(params: dict, cfg: ModelConfig, tokens: jax.Array,
                     caches: Dict[str, Any], offset: jax.Array,
                     moe_fn: Optional[MoeFn] = None
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Teacher-forced continuation: run ``tokens`` (B, S) at positions
    ``offset .. offset+S-1`` against caches whose first ``offset`` positions
    are valid — the whole suffix in ONE call instead of S ``decode_step``
    round-trips. Also serves as the long-prompt chunk step (advance
    ``offset`` between calls; with ``offset=0`` on a fresh cache this IS a
    bounded-shape prefill chunk) and, with a per-request ``offset`` (B,),
    as the MTP fused base+draft verification forward (divergent in-batch
    lengths). Returns (logits (B, S, V), new caches).

    Attention/MLA archs only: SSM state is not token-addressable. Callers
    must not pass *wrapped* ring caches (serving gates this path on
    ``attention.is_ring(cfg, capacity)`` — a ring buffer's wraparound write
    pattern is indistinguishable from a plain cache by shape alone, and a
    plain cache whose capacity merely equals ``sliding_window`` is fine)."""
    moe_fn = moe_fn or moe_mod.moe_capacity
    if cfg.is_ssm or cfg.is_hybrid or cfg.attention_kind not in ("causal",
                                                                 "mla"):
        raise NotImplementedError(
            "prefill_continue requires a causal-attention or MLA arch")
    x = params["embed"][tokens].astype(_dtype(cfg))
    b, s, _ = x.shape
    offset = jnp.asarray(offset, jnp.int32)
    new_caches: Dict[str, Any] = {}
    for seg in build_plan(cfg):
        seg_params = params["segments"][seg.name]
        cache = caches[seg.name]
        if cfg.attention_kind == "mla":
            def body(h, xs, seg=seg):
                pl, c = xs
                with jax.named_scope("attention"):
                    hin = rms_norm(h, pl["attn"]["ln"], cfg.norm_eps)
                    out, nc = mla_mod.mla_extend(pl["attn"], hin, c, offset,
                                                 cfg)
                h = h + out
                if seg.kind == "moe":
                    h, _ = _moe_block(pl["moe"], h, cfg, moe_fn)
                else:
                    h = _mlp_block(pl["mlp"], h, cfg)
                return h, nc

            x, new_mla = _scan(body, x, (seg_params, cache["mla"]))
            new_caches[seg.name] = {"mla": new_mla, "length": offset + s}
        else:
            def body(h, xs, seg=seg):
                pl, ck, cv = xs
                with jax.named_scope("attention"):
                    hin = rms_norm(h, pl["attn"]["ln"], cfg.norm_eps)
                    out, nk, nv = attn_mod.attention_extend(pl["attn"], hin,
                                                            ck, cv, offset, cfg)
                h = h + out
                if seg.kind == "moe":
                    h, _ = _moe_block(pl["moe"], h, cfg, moe_fn)
                else:
                    h = _mlp_block(pl["mlp"], h, cfg)
                return h, (nk, nv)

            x, (nk, nv) = _scan(body, x, (seg_params, cache.k, cache.v))
            new_caches[seg.name] = KVCache(nk, nv, offset + s)
    logits = unembed(params, cfg, x)
    return logits, new_caches


# ---------------------------------------------------------------------------
# Prefill (full sequence + cache materialization)
# ---------------------------------------------------------------------------


def _write_kv(tmpl: jax.Array, k: jax.Array, s: int, cache_dtype) -> jax.Array:
    """Write freshly-computed K or V (L,B,S,KV,hd) into a capacity buffer.

    Ring buffers (sliding-window serving at long context) place token p at
    slot p % cap, matching attention_decode's write pattern.
    """
    cap = tmpl.shape[2]
    if s <= cap:
        return jax.lax.dynamic_update_slice_in_dim(
            tmpl, k.astype(cache_dtype), 0, axis=2)
    last = k[:, :, -cap:].astype(cache_dtype)
    return jnp.roll(last, shift=s % cap, axis=2)


def prefill(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
            capacity: int, moe_fn: Optional[MoeFn] = None,
            cache_dtype=jnp.bfloat16) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt, return (logits (B,S,V), caches padded to capacity)."""
    moe_fn = moe_fn or moe_mod.moe_capacity
    x = embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    caches = make_caches(cfg, b, capacity, cache_dtype)
    new_caches: Dict[str, Any] = {}
    for seg in build_plan(cfg):
        x, _aux, segc = _seg_full(seg, params["segments"][seg.name],
                                  params.get("shared_attn"), x, cfg, moe_fn,
                                  positions, want_cache=True)
        tmpl = caches[seg.name]
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                buf = jax.lax.dynamic_update_slice_in_dim(
                    tmpl["mla"], segc.astype(cache_dtype), 0, axis=2)
                new_caches[seg.name] = {"mla": buf,
                                        "length": jnp.int32(s)}
            else:
                k, v = segc
                new_caches[seg.name] = KVCache(
                    _write_kv(tmpl.k, k, s, cache_dtype),
                    _write_kv(tmpl.v, v, s, cache_dtype), jnp.int32(s))
        elif seg.kind == "mamba_tail":
            hstate, conv = segc
            new_caches[seg.name] = SSMState(hstate, conv.astype(tmpl.conv.dtype),
                                            jnp.int32(s))
        else:
            (mh, mconv), (k, v) = segc
            nk = _write_kv(tmpl["shared_kv"].k, k, s, cache_dtype)
            nv = _write_kv(tmpl["shared_kv"].v, v, s, cache_dtype)
            new_caches[seg.name] = {
                "ssm": {"h": mh, "conv": mconv.astype(jnp.bfloat16),
                        "length": jnp.int32(s)},
                "length": jnp.int32(s),
                "shared_kv": KVCache(nk, nv, jnp.int32(s)),
            }
    logits = unembed(params, cfg, x)
    return logits, new_caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(params: dict, cfg: ModelConfig, batch: Dict[str, jax.Array],
            moe_fn: Optional[MoeFn] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, cfg, batch, moe_fn)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches" and "prefix_emb" in batch:
        logits = logits[:, batch["prefix_emb"].shape[1]:, :]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    loss = nll + cfg.router_aux_loss_coef * aux["aux_loss"]
    return loss, {"nll": nll, **aux}
