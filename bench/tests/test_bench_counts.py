"""The dense block's counts (arch/dense.py) and counts.py against
hand-computed FLOPs and bytes for granite-3-2b and for granite-3-8b-20l
(head_dim 128; a fixture until a cell uses it)."""
import copy

import benchpath
import pytest

import counts
import model_cfg

dense = model_cfg.load_arch("dense")

# per layer: D*H*hd + 2*D*KVH*hd + H*hd*D + 3*D*F, times 2 FLOPs, times L
HAND = {
    "granite-3-2b": dict(
        token=2 * 40 * (2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192),
        attn_per_key=4 * 40 * 32 * 64, logits=2 * 2048 * 49155,
        weights=2 * (49155 * 2048 + 40 * 60_817_408) + 81 * 2048 * 4,
        kv=2 * 40 * 8 * 64 * 2),
    "granite-3-8b-20l": dict(
        token=2 * 20 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12800),
        attn_per_key=4 * 20 * 32 * 128, logits=2 * 4096 * 49155,
        weights=2 * (49155 * 4096 + 20 * 199_229_440) + 41 * 4096 * 4,
        kv=2 * 20 * 8 * 128 * 2),
}
DIRS = {"granite-3-2b": model_cfg.CONFIG_DIR,
        "granite-3-8b-20l": benchpath.FIXTURES / "configs"}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand(name):
    spec, h = model_cfg.load(name, DIRS[name]), HAND[name]
    assert dense.token_flops(spec) == h["token"]
    assert dense.attn_flops(spec, 1000) == 1000 * h["attn_per_key"]
    assert dense.logits_flops(spec) == h["logits"]
    assert dense.weight_bytes(spec) == h["weights"]
    assert dense.kv_token_bytes(spec) == h["kv"]
    # two rows decoding at 100 and 300 keys
    w = dense.decode_work(spec, [100, 300])
    assert w.flops == 2 * (h["token"] + h["logits"]) + 400 * h["attn_per_key"]
    assert w.bytes == h["weights"] + 400 * h["kv"]
    # a first chunk of 4 tokens that ends its prompt: keys 1+2+3+4
    c = dense.chunk_work(spec, [(0, 4, True)])
    assert c.flops == 4 * h["token"] + 10 * h["attn_per_key"] + h["logits"]
    assert c.bytes == h["weights"] + 4 * h["kv"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_ignore_cache_len_and_pool(name):
    """Only live lengths count: the engine geometry (cache_len, pages, rows)
    that sizes the gathered copy does not enter."""
    spec = model_cfg.load(name, DIRS[name])
    other = copy.deepcopy(spec)
    other["engine"].update(num_pages=7, max_active=3, chunk_size=16, page_size=64)
    for s in (spec, other):
        assert dense.decode_work(s, [17, 2000]) == dense.decode_work(spec, [17, 2000])
        assert dense.chunk_work(s, [(32, 8, False)]) == dense.chunk_work(spec, [(32, 8, False)])


def test_least_seconds_names_the_bound():
    peak = counts.peaks("TPU v5 lite")
    assert counts.least_seconds(counts.Work(flops=197e12, bytes=1.0), peak) == (1.0, "compute")
    assert counts.least_seconds(counts.Work(flops=1.0, bytes=819e9), peak) == (1.0, "memory")
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")


def test_slot_parts_follow_the_request_timeline():
    """A 40-token prompt in chunks of 16 from slot 3, active in slot 5, 6
    tokens at 2 decode steps per slot: chunks at slots 3, 4, 5; tokens 1-2
    in slot 5, 3-4 in slot 6, 5 in slot 7."""
    spec = model_cfg.load("granite-3-2b")

    class R:
        tokens = [0] * 40
        start_slot, first_token_slot, generated = 3, 5, [1] * 6

    parts = counts.slot_parts(dense, spec, [R()], n_steps=2, chunk=16)
    assert sorted(parts) == [3, 4, 5, 6, 7]
    assert parts[3] == [dense.chunk_work(spec, [(0, 16, False)])]
    assert parts[5] == [dense.chunk_work(spec, [(32, 8, True)]),
                        dense.decode_work(spec, [41]), dense.decode_work(spec, [42])]
    assert parts[7] == [dense.decode_work(spec, [45])]
