"""Plain reference of an MLA + MoE decoder (DeepSeek-V3's layout, softmax
top-k router), in float32, layer by layer: the form a configuration file's
``reference`` key names. It imports nothing of the program; weights are
drawn again from the seed through ``bench/weights.py``.

Layers ``0 .. first_k_dense_replace - 1`` are dense, the rest MoE::

    h = rmsnorm(x) * ln
    q = (rmsnorm(h @ wq_a) * q_ln) @ wq_b          -> q_nope, q_rope per head
    c, k_rope = h @ wkv_a                          (latent, shared rope key)
    c = rmsnorm(c) * kv_ln
    k_nope, v = c @ wk_b, c @ wv_b                 (per head)
    q_rope, k_rope = rope(q_rope), rope(k_rope)    (rotate-half, rope_theta)
    x = x + softmax((q_nope k_nope + q_rope k_rope) / sqrt(nope + rope),
                    causal) v @ wo
    h = rmsnorm(x) * ln
    x = x + swiglu(h)                              (dense), or
    x = x + sum_k p_k swiglu_{e_k}(h) + swiglu_shared(h)
                                   (softmax over all experts, top-k,
                                    renormalised; dropless)

then ``logits = rmsnorm(x) * final_norm @ lm_head``. ``precision="fp8"``
rounds both operands of every weight product as ``bench/reference.py``.
"""
from __future__ import annotations

import functools
import types
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W

#: the program's segment names for the dense and the MoE layers
SEGMENTS = ("dense_lead", "moe")


def _attention(p, h, s, fp8):
    n, t, _ = h.shape
    nope, rope = s.nope, s.rope
    q = R._mm(R._rms(R._mm(h, p["wq_a"], fp8), p["q_ln"], s.eps),
              p["wq_b"], fp8).reshape(n, t, s.heads, nope + rope)
    kv = R._mm(h, p["wkv_a"], fp8)
    c = R._rms(kv[..., :s.kv_rank], p["kv_ln"], s.eps)
    k_nope = R._mm(c, p["wk_b"], fp8).reshape(n, t, s.heads, nope)
    v = R._mm(c, p["wv_b"], fp8).reshape(n, t, s.heads, s.v_dim)
    pos = jnp.arange(t)
    q_rope = R._rope(q[..., nope:], pos, s.theta)
    k_rope = R._rope(kv[..., s.kv_rank:][:, :, None, :], pos, s.theta)
    scores = (jnp.einsum("nqhe,nthe->nhqt", q[..., :nope], k_nope)
              + jnp.einsum("nqhe,ntze->nhqt", q_rope, k_rope))
    scores = scores / np.sqrt(nope + rope)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("nhqt,nthe->nqhe", jax.nn.softmax(scores, -1), v)
    return R._mm(o.reshape(n, t, s.heads * s.v_dim), p["wo"], fp8)


def _layer(p, x, s, fp8):
    x = x + _attention(p["attn"], R._rms(x, p["attn"]["ln"], s.eps), s, fp8)
    if "mlp" in p:
        m = p["mlp"]
        h = R._rms(x, m["ln"], s.eps)
        return x + R._swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)
    m = p["moe"]
    h = R._rms(x, m["ln"], s.eps)
    shared = R._swiglu(h, m["shared_gate"], m["shared_up"],
                       m["shared_down"], fp8)
    return x + R._moe(m, h, s, fp8) + shared


class Reference:
    """``Reference(conf, specs).logits(seed, tokens, rows, precision)``."""

    def __init__(self, conf: dict, specs: Sequence[Tuple[str, tuple, object]]):
        self.s = types.SimpleNamespace(
            d=conf["hidden_size"], heads=conf["num_attention_heads"],
            nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
            v_dim=conf["v_head_dim"], kv_rank=conf["kv_lora_rank"],
            eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
            experts=conf["n_routed_experts"],
            top_k=conf["num_experts_per_tok"],
            norm_topk=conf["norm_topk_prob"])
        self.layers = [(SEGMENTS[0], l)
                       for l in range(conf["first_k_dense_replace"])]
        self.layers += [(SEGMENTS[1], l) for l in range(
            conf["num_hidden_layers"] - conf["first_k_dense_replace"])]
        self.rules = conf.get("weights")
        self.leaves = {seg: [(path.split("/", 2)[2], path, shape[1:], dtype)
                             for path, shape, dtype in specs
                             if path.startswith(f"segments/{seg}/")]
                       for seg in SEGMENTS}
        self.other = {path: (shape, dtype) for path, shape, dtype in specs
                      if not W.layered(path)}

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _draw_layer(self, key, seg, layer):
        out = {}
        for name, path, shape, dtype in self.leaves[seg]:
            group, leaf = name.split("/")
            out.setdefault(group, {})[leaf] = W.draw_layer(
                key, path, layer, shape, dtype, self.rules).astype(jnp.float32)
        return out

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _draw(self, path, key):
        shape, dtype = self.other[path]
        return W.draw(W.leaf_key(key, path), path, shape, dtype,
                      self.rules).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _run_layer(self, p, x, fp8):
        with jax.default_matmul_precision("highest"):
            return _layer(p, x, self.s, fp8)

    @functools.partial(jax.jit, static_argnums=(0, 5))
    def _head(self, x, rows, norm, head, fp8):
        with jax.default_matmul_precision("highest"):
            h = R._rms(x[rows[:, 0], rows[:, 1]], norm, self.s.eps)
            return R._mm(h, head, fp8)

    def logits(self, seed: int, tokens: np.ndarray, rows: np.ndarray,
               precision: str = "float32") -> np.ndarray:
        fp8 = precision == "fp8"
        key = W.base_key(seed)
        x = self._draw("embed", key)[jnp.asarray(tokens)]
        for seg, layer in self.layers:
            x = self._run_layer(self._draw_layer(key, seg, layer), x, fp8)
        out = self._head(x, jnp.asarray(rows, jnp.int32),
                         self._draw("final_norm", key),
                         self._draw("lm_head", key), fp8)
        return np.asarray(out, np.float32)
