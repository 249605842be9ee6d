"""Operations and bytes of the decoder configurations, from their shapes.

Everything here reads a configuration file of ``bench/configs`` (Hugging
Face key names) and nothing of the program. A multiply-add counts two
operations. Bytes are at the configuration's dtype (``torch_dtype``), not at
whatever precision the program happens to keep a cache or a router in.
"""
from __future__ import annotations

from typing import Iterable

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _sizes(conf: dict):
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return d, h, kv, hd


def dtype_bytes(conf: dict) -> int:
    return DTYPE_BYTES[conf["torch_dtype"]]


def attn_params(conf: dict) -> int:
    """Projections of one layer (q, k, v, o), without norms."""
    d, h, kv, hd = _sizes(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(conf: dict) -> int:
    """One expert (or the dense MLP): gate, up and down."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def layer_norm_params(conf: dict) -> int:
    d, _, _, hd = _sizes(conf)
    return 2 * d + (2 * hd if conf.get("qk_norm") == "per_head" else 0)


def router_params(conf: dict) -> int:
    return conf["hidden_size"] * conf.get("num_experts", 0)


def layer_params(conf: dict) -> int:
    e = conf.get("num_experts", 0)
    ffn = e * expert_params(conf) + router_params(conf) if e \
        else expert_params(conf)
    return attn_params(conf) + ffn + layer_norm_params(conf)


def param_count(conf: dict) -> int:
    d, v = conf["hidden_size"], conf["vocab_size"]
    head = 0 if conf["tie_word_embeddings"] else v * d
    return v * d + head + d + conf["num_hidden_layers"] * layer_params(conf)


def token_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through, the head excluded: attention
    projections and, per layer, the router and its top-k experts (or the
    dense MLP)."""
    e = conf.get("num_experts", 0)
    ffn = (conf["num_experts_per_tok"] * expert_params(conf)
           + router_params(conf)) if e else expert_params(conf)
    return conf["num_hidden_layers"] * (attn_params(conf) + ffn)


def head_flops(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def attention_flops(conf: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys."""
    _, h, _, hd = _sizes(conf)
    return conf["num_hidden_layers"] * 4 * h * hd * context


def token_flops(conf: dict, context: int, head: bool = True) -> int:
    """One token at position ``context - 1`` (it attends to ``context``
    keys, itself included)."""
    return (2 * token_matmul_params(conf) + attention_flops(conf, context)
            + (head_flops(conf) if head else 0))


def prefill_flops(conf: dict, start: int, end: int) -> int:
    """Tokens ``start .. end - 1`` of a prompt computed against the cache of
    ``start`` reused tokens; the head runs for the last token only (the
    first output token). Reused tokens cost nothing."""
    n = end - start
    if n <= 0:
        return 0
    ctx_sum = n * (start + end + 1) // 2       # sum of (p + 1), p in range
    _, h, _, hd = _sizes(conf)
    return (2 * token_matmul_params(conf) * n
            + conf["num_hidden_layers"] * 4 * h * hd * ctx_sum
            + head_flops(conf))


def kv_bytes_per_token(conf: dict) -> int:
    _, _, kv, hd = _sizes(conf)
    return conf["num_hidden_layers"] * 2 * kv * hd * dtype_bytes(conf)


def decode_iteration(conf: dict, contexts: Iterable[int]):
    """(operations, bytes) of one decode iteration over the live slots,
    each at its context length (keys attended, the new token included).

    Bytes: every weight the iteration multiplies through once (embedding
    rows of the live tokens; per layer the attention, the router and the
    experts the live tokens can reach, at most all of them; the head),
    plus the keys and values of every live token's context."""
    contexts = list(contexts)
    n = len(contexts)
    if not n:
        return 0, 0
    d, v, layers = conf["hidden_size"], conf["vocab_size"], \
        conf["num_hidden_layers"]
    e = conf.get("num_experts", 0)
    if e:
        reach = min(e, n * conf["num_experts_per_tok"])
        ffn = reach * expert_params(conf) + router_params(conf)
    else:
        ffn = expert_params(conf)
    weights = (layers * (attn_params(conf) + ffn + layer_norm_params(conf))
               + v * d + d + n * d)
    ops = sum(token_flops(conf, c) for c in contexts)
    nbytes = weights * dtype_bytes(conf) + kv_bytes_per_token(conf) * sum(contexts)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
