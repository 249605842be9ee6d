"""Staged hybrid parallelism (SP→TP→SP) for MLA prefill — paper §4.3.1.

Pure data parallelism for prefill MLA suffers sequence-length skew and
insufficient concurrency (paper Fig. 16a). The staged scheme instead:

* **Stage 1 (SP)** — packed tokens are sharded *by sequence* over the model
  axis; per-token work (input RMSNorm + the down-projections wq_a / wkv_a,
  i.e. MLAProlog's front half) is perfectly load-balanced regardless of
  request lengths.
* **All-Gather** — performed *after* dimensionality reduction (the latents
  are q_lora_rank=1536 and kv_lora_rank+rope=576 wide vs d_model=7168), so
  the collective moves ~3.5× less than gathering hidden states. This is the
  paper's own justification for the placement.
* **Stage 2 (TP)** — attention heads are sharded over the model axis; each
  rank expands the latents for its H/m heads (unabsorbed MHA form, as the
  paper uses for prefill) and runs full-sequence chunked attention.
* **Stage 3 (SP)** — two variants:
    - ``oproj_mode="a2a"`` (paper-faithful Fig. 17): All-to-All reshards
      head-sharded outputs back to sequence shards, then o_proj runs locally.
    - ``oproj_mode="rs"`` (beyond-paper): o_proj is computed in TP form on
      head shards and reduce-scattered over the sequence — moves D=7168
      floats/token instead of H·v_d=16384, a ~2.3× collective saving.
      Recorded separately in EXPERIMENTS.md §Perf.

Returns sequence-sharded outputs and the latent KV cache (already in the
layout the decode path consumes).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.attention import NEG_INF, _pick_chunk
from repro.models.layers import apply_rope, rms_norm
from repro.models.mla import softmax_scale


def mla_prefill_hybrid(p: dict, x: jax.Array, cfg: ModelConfig, mesh: Mesh,
                       axis: str = "model", oproj_mode: str = "a2a"
                       ) -> Tuple[jax.Array, jax.Array]:
    """p: single-layer MLA params; x: (B, S, D) with S sharded over ``axis``.

    Returns (out (B,S,D) seq-sharded, latent cache (B,S,kvr+rope) seq-sharded).
    """
    assert oproj_mode in ("a2a", "rs")
    h = cfg.num_heads
    m = mesh.shape[axis]
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    scale = softmax_scale(cfg)

    def body(x_loc, wq_a, q_ln, wq_b, wkv_a, kv_ln, wk_b, wv_b, wo):
        # x_loc is the already-normed layer input (caller applies the layer
        # RMSNorm, matching the mla_prefill interface); being per-token, that
        # norm is itself sequence-parallel under the same sharding.
        b, s_loc, d = x_loc.shape
        rank = jax.lax.axis_index(axis)
        pos_loc = rank * s_loc + jnp.arange(s_loc, dtype=jnp.int32)

        # ---- Stage 1 (SP): latent down-projections on sequence shards ----
        xin = x_loc
        q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", xin, wq_a), q_ln, cfg.norm_eps)
        kv = jnp.einsum("bsd,dr->bsr", xin, wkv_a)
        c_kv = rms_norm(kv[..., :kvr], kv_ln, cfg.norm_eps)
        k_rope = apply_rope(kv[..., kvr:][:, :, None, :],
                            jnp.broadcast_to(pos_loc, (b, s_loc)),
                            cfg.rope_theta, cfg.yarn)[:, :, 0, :]
        latent_loc = jnp.concatenate([c_kv, k_rope], axis=-1)

        # ---- All-Gather (post-reduction latents, paper-placed) ----
        q_lat_full = jax.lax.all_gather(q_lat, axis, axis=1, tiled=True)
        latent_full = jax.lax.all_gather(latent_loc, axis, axis=1, tiled=True)
        s = s_loc * m
        pos_full = jnp.arange(s, dtype=jnp.int32)

        # ---- Stage 2 (TP over heads): expand latents, chunked attention ----
        h_loc = h // m
        q = jnp.einsum("bsr,re->bse", q_lat_full, wq_b)
        q = q.reshape(b, s, h_loc, nope + rope)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = apply_rope(q_rope, jnp.broadcast_to(pos_full, (b, s)),
                            cfg.rope_theta, cfg.yarn)
        c_full, kr_full = latent_full[..., :kvr], latent_full[..., kvr:]
        k_nope = jnp.einsum("bsr,re->bse", c_full, wk_b).reshape(b, s, h_loc, nope)
        v = jnp.einsum("bsr,re->bse", c_full, wv_b).reshape(b, s, h_loc, vd)

        chunk = _pick_chunk(s)
        nc = s // chunk

        def one_chunk(ci):
            qp = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
            qn = jax.lax.dynamic_slice_in_dim(q_nope, ci * chunk, chunk, axis=1)
            qrp = jax.lax.dynamic_slice_in_dim(q_rope, ci * chunk, chunk, axis=1)
            scores = (jnp.einsum("bshe,bthe->bhst", qn.astype(jnp.float32),
                                 k_nope.astype(jnp.float32))
                      + jnp.einsum("bshe,bte->bhst", qrp.astype(jnp.float32),
                                   kr_full.astype(jnp.float32))) * scale
            mask = pos_full[None, :] <= qp[:, None]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bhst,bthe->bshe", probs, v.astype(jnp.float32))

        if nc == 1:
            out_h = one_chunk(jnp.int32(0))
        else:
            from repro.models.scan_util import chunk_map
            outs = chunk_map(one_chunk, nc)
            out_h = jnp.moveaxis(outs, 0, 1).reshape(b, s, h_loc, vd)
        out_h = out_h.astype(x_loc.dtype)                    # (B, S, H_loc, vd)

        # ---- Stage 3 (back to SP) ----
        if oproj_mode == "a2a":
            # Paper Fig. 17: All-to-All head-shards -> sequence-shards,
            # then o_proj locally over all heads. wo arrives replicated.
            out_seq = jax.lax.all_to_all(out_h, axis, split_axis=1,
                                         concat_axis=2, tiled=True)
            out = jnp.einsum("bse,ed->bsd",
                             out_seq.reshape(b, s_loc, h * vd), wo)
        else:
            # Beyond-paper: TP o_proj on head shards + reduce-scatter over
            # the sequence (moves D instead of H*vd floats per token).
            partial = jnp.einsum("bshe,hed->bsd", out_h,
                                 wo.reshape(h_loc, vd, d))
            out = jax.lax.psum_scatter(partial, axis, scatter_dimension=1,
                                       tiled=True)
        return out, latent_loc

    wo_spec = P() if oproj_mode == "a2a" else P("model", None)
    out, latent = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None),            # x: sequence-sharded
                  P(), P(), P(None, axis),        # wq_a, q_ln, wq_b(heads)
                  P(), P(), P(None, axis),        # wkv_a, kv_ln, wk_b(heads)
                  P(None, axis), wo_spec),        # wv_b(heads), wo
        out_specs=(P(None, axis, None), P(None, axis, None)),
        check_vma=False,
    )(x, p["wq_a"], p["q_ln"], p["wq_b"], p["wkv_a"], p["kv_ln"],
      p["wk_b"], p["wv_b"], p["wo"])
    return out, latent
