"""Operations and bytes of DeepSeek-V3 on one chip of an expert-parallel
deployment, from its configuration file alone (the file's ``counts`` key).

The file states the experts held here (``n_routed_experts``) and, under
``program.fields``, the router's width (``router_experts``; the held count
where absent). The first ``first_k_dense_replace`` layers are dense, of width
``intermediate_size``; the rest route to experts of width
``moe_intermediate_size`` and add one shared expert of that width. The
router scores every expert and has a selection bias of one value an expert.

Every token multiplies through the same projections in either form of MLA
(prefill expands the latent through ``wk_b`` and ``wv_b``; decode absorbs
them into the query and the output). Attention over ``c`` keys costs
``2 h c (nope + rope + v)`` in prefill and ``2 h c (2 kv_rank + rope)`` in
decode, which reads the latent cache of ``kv_rank + rope`` values a token and
layer. A token's routed experts here are its expected routings onto the held
experts, ``top_k * held / router width`` (the routing itself is not in the
file). A decode iteration reads the held experts that its live tokens can
reach, ``min(held, n * top_k)``. Bytes are at ``torch_dtype``.
"""
from __future__ import annotations

from typing import Iterable

from bench.counts import dtype_bytes, head_flops, least_seconds  # noqa: F401


def _mla(conf):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["q_lora_rank"], conf["kv_lora_rank"],
            conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
            conf["v_head_dim"])


def attn_params(conf: dict) -> int:
    d, h, qr, kvr, nope, rope, vd = _mla(conf)
    return (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
            + kvr * h * (nope + vd) + h * vd * d)


def attn_norm_params(conf: dict) -> int:
    """The layer's two RMSNorm gains, and MLA's of the query and latent."""
    return 2 * conf["hidden_size"] + conf["q_lora_rank"] + conf["kv_lora_rank"]


def dense_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def held(conf: dict) -> int:
    return conf["n_routed_experts"]


def router_width(conf: dict) -> int:
    return conf["program"].get("fields", {}).get("router_experts") \
        or held(conf)


def router_params(conf: dict) -> int:
    """The router's matrix and its selection bias."""
    return (conf["hidden_size"] + 1) * router_width(conf)


def layers(conf: dict):
    """(dense layers, MoE layers)."""
    k = conf["first_k_dense_replace"]
    return k, conf["num_hidden_layers"] - k


def ffn_params(conf: dict, experts: int) -> int:
    """One MoE layer's FFN with ``experts`` held experts read."""
    return ((experts + conf["n_shared_experts"]) * expert_params(conf)
            + router_params(conf))


def param_count(conf: dict) -> int:
    d, v = conf["hidden_size"], conf["vocab_size"]
    dense, moe = layers(conf)
    head = 0 if conf["tie_word_embeddings"] else v * d
    per = attn_params(conf) + attn_norm_params(conf)
    return (v * d + head + d
            + dense * (per + dense_params(conf))
            + moe * (per + ffn_params(conf, held(conf))))


def token_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through here, the head excluded: the
    attention projections, the dense FFNs, and per MoE layer the router,
    the shared expert and its expected routings onto held experts."""
    dense, moe = layers(conf)
    routed = (conf["num_experts_per_tok"] * held(conf) * expert_params(conf)
              // router_width(conf))
    return (conf["num_hidden_layers"] * attn_params(conf)
            + dense * dense_params(conf)
            + moe * (routed + conf["n_shared_experts"] * expert_params(conf)
                     + conf["hidden_size"] * router_width(conf)))


def prefill_flops(conf: dict, start: int, end: int) -> int:
    """Tokens ``start .. end - 1`` against a cache of ``start`` reused
    tokens; the head runs for the last token only."""
    n = end - start
    if n <= 0:
        return 0
    _, h, _, _, nope, rope, vd = _mla(conf)
    ctx_sum = n * (start + end + 1) // 2
    return (2 * token_matmul_params(conf) * n
            + conf["num_hidden_layers"] * 2 * h * (nope + rope + vd) * ctx_sum
            + head_flops(conf))


def latent_bytes_per_token(conf: dict) -> int:
    return (conf["num_hidden_layers"]
            * (conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
            * dtype_bytes(conf))


def decode_iteration(conf: dict, contexts: Iterable[int]):
    """(operations, bytes) of one decode iteration over the live slots,
    each at its context length (keys attended, the new token included)."""
    contexts = list(contexts)
    n = len(contexts)
    if not n:
        return 0, 0
    d, h, _, kvr, _, rope, _ = _mla(conf)
    dense, moe = layers(conf)
    reach = min(held(conf), n * conf["num_experts_per_tok"])
    weights = (conf["num_hidden_layers"]
               * (attn_params(conf) + attn_norm_params(conf))
               + dense * dense_params(conf)
               + moe * ffn_params(conf, reach)
               + conf["vocab_size"] * d + d + n * d)
    ops = sum(2 * token_matmul_params(conf)
              + conf["num_hidden_layers"] * 2 * h * c * (2 * kvr + rope)
              + head_flops(conf) for c in contexts)
    nbytes = (weights * dtype_bytes(conf)
              + latent_bytes_per_token(conf) * sum(contexts))
    return ops, nbytes
