"""Architecture modules, found by the configuration's ``arch``.

The dense block in ``arch/dense.py`` gives what the benchmark gave before it
moved there (``make_arch_golden.py``: weight digests, reference logits and
counts, recorded then); an architecture brought as files alone
(``fixtures/arch/tiny-untied.py``) is served through the harness on the CPU
and checked; a missing architecture, a missing contract function and a file
with no ``arch`` fail by name."""
import json
import time

import benchpath
import jax
import numpy as np
import pytest

import counts
import harness
import make_arch_golden as G
import model_cfg
import weights

FIX = benchpath.FIXTURES
GOLDEN = json.loads(G.GOLDEN.read_text())
dense = model_cfg.load_arch("dense")


@pytest.mark.parametrize("seed", G.SEEDS)
def test_dense_weights_match_golden(seed):
    tiny = G.spec_of("tiny-dense", FIX / "configs")
    assert G.digests(weights.make_flat(dense, tiny, seed)) == GOLDEN["weights"][str(seed)]


@pytest.mark.parametrize("kind", ["served", "int8"])
def test_dense_reference_matches_golden(kind):
    tiny = G.spec_of("tiny-dense", FIX / "configs")
    flat = weights.make_flat(dense, tiny, G.SEEDS[0])
    got = dense.logits_at(tiny, flat, G.IDS, G.ROWS, G.PAD_TO, G.ROWS_PAD,
                          int8=kind == "int8")
    # the same XLA program on another CPU may change the last bit, no more
    np.testing.assert_allclose(got, np.load(G.LOGITS)[kind], rtol=1e-6, atol=0)


def test_dense_counts_match_golden():
    g2b = model_cfg.load("granite-3-2b")
    assert [[w.flops, w.bytes] for w in (dense.chunk_work(g2b, c) for c in G.CHUNKS)] \
        == GOLDEN["chunk_work"]
    assert [[w.flops, w.bytes] for w in (dense.decode_work(g2b, c) for c in G.CONTEXTS)] \
        == GOLDEN["decode_work"]
    parts = counts.slot_parts(dense, g2b, [G.Req(*r) for r in G.REQUESTS],
                              G.N_STEPS, G.CHUNK)
    assert {str(t): [[w.flops, w.bytes] for w in ws] for t, ws in parts.items()} \
        == GOLDEN["slot_parts"]


def _manifest(config: str) -> dict:
    spec = json.loads((FIX / "configs" / f"{config}.json").read_text())
    return {"workloads": [{"name": f"{config}-chat", "config": config,
                           "traffic": "tiny-chat", "chips": 1, "why": "t"}],
            "configs": [{"name": config, "reduced": spec["reduced"]}],
            "end_to_end": [], "per_layer": []}


@pytest.fixture(scope="module")
def untied():
    cell = harness.load_cell(_manifest("tiny-untied"), "tiny-untied-chat", FIX)
    return harness.prepare(cell, 1, {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def test_untied_served_and_correct(untied):
    """The fixture's own head reaches the program, and the served tokens
    agree with the fixture's reference through it."""
    assert untied.cell.arch.HEAD == "embed/head"
    assert untied.params["embed"]["head"] is untied.flat["embed/head"]
    data, notes = harness.window(untied, 1, 3.0, time.perf_counter())
    assert len(data.done()) == len(data.served) == 18
    assert notes["window_lowerings"] == 0
    assert data.arch is untied.cell.arch
    # the fixture's counts, not the dense block's: the same FLOPs, and the
    # head's bytes besides in every step that computes logits
    reqs = [s.req for s in data.done()]
    ours, theirs = (counts.slot_parts(a, data.spec, reqs, data.n_steps, data.chunk)
                    for a in (data.arch, dense))
    assert ours.keys() == theirs.keys()
    head = 128 * 512 * 2
    extra = [o.bytes - t.bytes for s in ours for o, t in zip(ours[s], theirs[s], strict=True)
             if o.flops == t.flops]
    assert len(extra) == sum(map(len, ours.values())) and set(extra) == {0, head}
    verdict = harness.check(untied, data, 1)
    assert verdict["correct"], verdict


def test_untied_altered_token_is_caught(untied, monkeypatch):
    from repro.runtime import engine as E

    greedy = E._sync_next
    V = untied.cell.spec["vocab_size"]
    monkeypatch.setattr(E, "_sync_next", lambda sig, logits, samp, sync:
                        (greedy(sig, logits, samp, sync) + 1) % V)
    jax.clear_caches()
    try:
        harness.reseed(untied, 2)
        data, _ = harness.window(untied, 2, 3.0, time.perf_counter())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    verdict = harness.check(untied, data, 2)
    assert not verdict["correct"]
    assert verdict["checks"]["logit_gap_max"]["value"] > \
        verdict["checks"]["logit_gap_max"]["limit"]


def _bench_dir(tmp_path, spec: dict):
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / f"{spec['name']}.json").write_text(json.dumps(spec))
    return tmp_path


def test_unknown_arch_fails_with_its_path(tmp_path):
    spec = dict(G.spec_of("tiny-dense", FIX / "configs"), arch="no-such-block")
    with pytest.raises(FileNotFoundError, match="no-such-block.py"):
        harness.load_cell(_manifest("tiny-dense"), "tiny-dense-chat",
                          _bench_dir(tmp_path, spec))


def test_contract_function_missing_fails_by_name(tmp_path):
    (tmp_path / "arch").mkdir()
    src = (model_cfg.ARCH_DIR / "dense.py").read_text()
    (tmp_path / "arch" / "nodecode.py").write_text(
        src.replace("def decode_work(", "def _decode_work("))
    spec = dict(G.spec_of("tiny-dense", FIX / "configs"), arch="nodecode")
    with pytest.raises(ValueError, match="decode_work"):
        harness.load_cell(_manifest("tiny-dense"), "tiny-dense-chat",
                          _bench_dir(tmp_path, spec))


def test_config_without_arch_fails(tmp_path):
    spec = G.spec_of("tiny-dense", FIX / "configs")
    del spec["arch"]
    with pytest.raises(ValueError, match="arch"):
        model_cfg.load("tiny-dense", _bench_dir(tmp_path, spec) / "configs")
