"""The traffic generator: seeded, in range, shared where the mix says."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

SEED = 2**31 + 12345           # past 32 signed bits, as the driver's are
MIXES = ("rag-prefix", "unshared", "chat-decode")


def gen(mix, seed=SEED, vocab=49155):
    traffic = harness.load_json(os.path.join(ROOT, "bench", "traffic",
                                             mix + ".json"))
    mod = harness.load_module(os.path.join(
        ROOT, "bench", "traffic", traffic["generator"] + ".py"),
        "bench_traffic_" + traffic["generator"])
    return traffic, mod.Generator(traffic, seed, vocab)


def stream(g, waves=3):
    return [[(r.rid, r.prompt, r.max_new, r.document) for r in g.wave(w)]
            for w in range(waves)]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_stream(mix):
    _, a = gen(mix)
    _, b = gen(mix)
    assert stream(a) == stream(b)
    assert a.documents == b.documents
    assert [(r.prompt, r.max_new) for r in a.warmup()] == \
        [(r.prompt, r.max_new) for r in b.warmup()]
    _, c = gen(mix, SEED + 1)
    assert stream(c) != stream(a)
    # another seed draws other tokens for the same sizes in the same order
    assert [[(len(p), n) for _, p, n, _ in w] for w in stream(c)] == \
        [[(len(p), n) for _, p, n, _ in w] for w in stream(a)]


@pytest.mark.parametrize("mix", MIXES)
def test_every_wave_holds_the_same_sizes(mix):
    _, a = gen(mix)
    _, b = gen(mix, 7)
    sizes = {tuple(sorted((len(r.prompt), r.max_new) for r in g.wave(w)))
             for g in (a, b) for w in range(4)}
    # prompt and output lengths pair up differently, but each multiset holds
    per = [sorted(len(r.prompt) for r in g.wave(w)) for g in (a, b)
           for w in range(4)]
    outs = [sorted(r.max_new for r in g.wave(w)) for g in (a, b)
            for w in range(4)]
    assert all(p == per[0] for p in per) and all(o == outs[0] for o in outs)
    assert len(sizes) >= 1


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_in_range_and_on_the_step(mix):
    traffic, g = gen(mix)
    suf, out = traffic["suffix_tokens"], traffic["output_tokens"]
    doc = traffic.get("documents", {}).get("tokens", 0)
    reqs = [r for w in range(4) for r in g.wave(w)] + g.warmup()
    for r in reqs:
        s = len(r.prompt) - doc
        assert suf["min"] <= s <= suf["max"] and (s - suf["min"]) % suf["step"] == 0
        assert len(r.prompt) % 32 == 0
        assert all(0 <= t < 49155 for t in r.prompt)
    for r in reqs[: -len(g.warmup())]:
        assert out["min"] <= r.max_new <= out["max"]
    assert len(g.wave(0)) == traffic["clients"]
    # the warm-up sends every prompt length the waves send
    assert {len(r.prompt) for r in g.warmup()} == \
        {len(r.prompt) for w in range(4) for r in g.wave(w)}


def test_rag_prefix_shares_documents_by_zipf():
    traffic, g = gen("rag-prefix")
    docs = traffic["documents"]
    assert len(g.documents) == docs["count"]
    assert len({tuple(d) for d in g.documents}) == docs["count"]
    for w in range(3):
        wave = g.wave(w)
        counts = [0] * docs["count"]
        for r in wave:
            assert r.prompt[:docs["tokens"]] == g.documents[r.document]
            counts[r.document] += 1
        # 16 clients over 8 documents, rank ** -1: 5.9, 2.9, 2.0, 1.5, ...
        assert counts == [6, 3, 2, 1, 1, 1, 1, 1]
        suffixes = [tuple(r.prompt[docs["tokens"]:docs["tokens"] + 8])
                    for r in wave]
        assert len(set(suffixes)) == len(wave)


@pytest.mark.parametrize("mix", ["unshared", "chat-decode"])
def test_unshared_mixes_share_no_block(mix):
    _, g = gen(mix)
    assert g.documents == []
    heads = [tuple(r.prompt[:8]) for w in range(4) for r in g.wave(w)]
    heads += [tuple(r.prompt[:8]) for r in g.warmup()]
    assert len(set(heads)) == len(heads)
