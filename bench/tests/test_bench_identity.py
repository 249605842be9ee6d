"""What the existing configurations read, pinned bit for bit: the weights
drawn from a seed, the reference's logits in float32 and in fp8, the counts
of operations and bytes, and the program's config. Each configuration file
is shrunk to a CPU size that keeps its architecture (GQA with a dense MLP;
GQA with per-head qk-norm and a softmax top-k router); the counts and the
config are pinned at the files' own sizes too. A change to how the harness
finds or applies a configuration's parts must leave every digest here
as it is."""
import dataclasses
import hashlib
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts, harness  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import weights as W  # noqa: E402

SEED = 2**33 + 2**31 + 17
#: the CPU size of each file: every key named here is set, the rest kept
SHRINK = {
    "granite-3-2b": {"hidden_size": 128, "intermediate_size": 256,
                     "num_hidden_layers": 2, "num_attention_heads": 4,
                     "num_key_value_heads": 2, "vocab_size": 512},
    "olmoe-1b-7b.half": {"hidden_size": 128, "intermediate_size": 64,
                         "num_hidden_layers": 2, "num_attention_heads": 4,
                         "num_key_value_heads": 4, "num_experts": 16,
                         "num_experts_per_tok": 4, "vocab_size": 512},
}
CONTEXTS = [1, 37, 100, 513]

PINNED = {
    "granite-3-2b": {
        "weights":
            "eb1bb93aa2d8649e79fdf066ea8b236a737247d5855268d4bca50111c1efb3c5",
        "logits_float32":
            "36143fc5989cf32ee675ddb51ccc58e03c4353bfe1953a52346e39c9418c37ed",
        "logits_fp8":
            "4b8521fe8f7b06553949a168d4319e2a9ed7917b6eaadd3cb982400465106fd2",
        "config":
            "b6a6fc7f3f5411a34e4a12d325d63adc48bf2ace5e29e148d61fdc11c9bfec75",
        "config_full":
            "c0fdf16c1e69b82f7c50e625649e2e022bb0c4e00d8edb73e0ff772bfb4a70dd",
        "counts": {
            "decode_iteration": [
                [721920, 722944],
                [1480704, 742144],
                [2304000, 793600],
                [3550208, 1056512],
            ],
            "prefill_flops": [721920, 64284672,
                              39852032, 151191552],
            "param_count": 361088},
        "counts_full": {
            "decode_iteration": [
                [5067059200, 5067149312],
                [10145914880, 5070184448],
                [15245414400, 5078380544],
                [20480245760, 5120409600],
            ],
            "prefill_flops": [5067059200, 488395386880,
                              293510983680, 647151759360],
            "param_count": 2533531648},
    },
    "olmoe-1b-7b.half": {
        "weights":
            "33e5d4e79763f6d636cb1a3a332aae5eb8a7e027a00305cfa94eafa8cda9f912",
        "logits_float32":
            "efb3db30d16817136553a2cb0a011da1bbe8c78bc9a7fd311a1fffe8cc43faf8",
        "logits_fp8":
            "6314e58867413f8c27b7b16bf07a4225c3103c59ad96ee7ff7aa43549a5ebaba",
        "config":
            "6d6a4c181e7519342b64727f37c9ef3ea49a7c338e0e3299be4364f3ffc1b99a",
        "config_full":
            "b5f923b1392e45e38399770744707801ef0d0897dc539b458ddacd2d59358fd3",
        "counts": {
            "decode_iteration": [
                [795648, 797440],
                [1628160, 1228800],
                [2525184, 1724672],
                [3845120, 2643456],
            ],
            "prefill_flops": [795648, 71657472,
                              44275712, 160628736],
            "param_count": 1053440},
        "counts_full": {
            "decode_iteration": [
                [1281949696, 1282027520],
                [2566258688, 2089762816],
                [3854696448, 2901626880],
                [5170200576, 3740557312],
            ],
            "prefill_flops": [1281949696, 108120899584,
                              65033601024, 142749466624],
            "param_count": 3562573824},
    },
}


def full(name):
    return harness.load_json(os.path.join(ROOT, "bench", "configs",
                                          name + ".json"))


def shrunk(name):
    return dict(full(name), **SHRINK[name])


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def config_digest(conf) -> str:
    cfg = harness.program_config(conf)
    return hashlib.sha256(repr(dataclasses.asdict(cfg)).encode()).hexdigest()


def count_readings(conf):
    return {"decode_iteration": [
                list(counts.decode_iteration(conf, CONTEXTS[:n]))
                for n in range(1, len(CONTEXTS) + 1)],
            "prefill_flops": [counts.prefill_flops(conf, a, b)
                              for a, b in ((0, 1), (0, 100), (40, 100),
                                           (512, 640))],
            "param_count": counts.param_count(conf)}


def check_tokens(vocab):
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, vocab, (2, 24)).astype(np.int32)
    rows = np.array([(0, 0), (0, 11), (0, 23), (1, 5), (1, 23)], np.int32)
    return tokens, rows


@pytest.mark.parametrize("name", sorted(PINNED))
def test_configuration_reads_what_it_always_read(name):
    conf, pinned = shrunk(name), PINNED[name]
    assert config_digest(conf) == pinned["config"]
    assert config_digest(full(name)) == pinned["config_full"]
    assert count_readings(conf) == pinned["counts"]
    assert count_readings(full(name)) == pinned["counts_full"]

    shapes = harness.param_shapes(harness.program_config(conf))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        W.make_params(shapes, SEED))
    assert digest(*(a for _, a in flat)) == pinned["weights"]

    ref = R.Reference(conf, W.leaf_specs(shapes))
    tokens, rows = check_tokens(conf["vocab_size"])
    assert digest(ref.logits(SEED, tokens, rows)) == pinned["logits_float32"]
    assert digest(ref.logits(SEED, tokens, rows, precision="fp8")) \
        == pinned["logits_fp8"]
