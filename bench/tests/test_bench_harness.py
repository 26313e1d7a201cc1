"""The harness at a tiny size on the CPU: the program's serve loop over the
window, the metric arithmetic, and the comparison that decides ``correct``
against the int8 control and against faults planted in the timed path."""
import json
import time

import benchpath
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

FIX = benchpath.FIXTURES
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SECONDS = 3.0


@pytest.fixture(scope="module")
def setup():
    spec = json.loads((FIX / "configs" / "tiny-dense.json").read_text())
    man = {"workloads": [{"name": "tiny-chat", "config": "tiny-dense",
                          "traffic": "tiny-chat", "chips": 1, "why": "t"}],
           "configs": [{"name": "tiny-dense", "reduced": spec["reduced"]}],
           "end_to_end": [], "per_layer": []}
    cell = harness.load_cell(man, "tiny-chat", FIX)
    return harness.prepare(cell, 1, PEAK)


def _window(setup, seed, drain_s=90.0):
    harness.reseed(setup, seed)
    return harness.window(setup, seed, SECONDS, time.perf_counter(), drain_s=drain_s)


def test_window_serves_through_the_program_loop(setup):
    data, notes = _window(setup, 1)
    n = len(data.served)
    assert n == 18 and len(data.done()) == n and not any(s.refused for s in data.served)
    # every slot went through step_slot_chunked, on the host clock, in order
    slots = sorted(data.starts)
    assert slots == list(range(len(slots))) and notes["dispatching"]
    assert all(data.starts[t] <= data.ends[t] <= data.starts.get(t + 1, np.inf)
               for t in slots)
    assert set(notes["rates"]) <= {1.0, 2.0, 3.0, 4.0, 5.0}   # Algorithm 1's actions
    assert notes["window_lowerings"] == 0
    for s in data.done():
        r = s.req
        assert len(r.generated) == s.max_new_tokens
        # the last token's slot is the slot the readback retired it in
        assert data.token_slot(r, len(r.generated) - 1) == r.finish_slot
        assert data.ttft_s(s) > 0 and data.queue_wait_s(s) >= -1e-9
        assert data.ttft_s(s) >= data.queue_wait_s(s)
    m = harness.read_metrics(
        [{"name": k, "unit": "u"} for k in
         ("ttft_p50_ms", "tpot_p50_ms", "output_tok_s", "served_share", "setup_s")],
        "e2e_metrics", data)
    ttft = sorted(1e3 * data.ttft_s(s) for s in data.done())
    assert m["ttft_p50_ms"]["value"] == pytest.approx(np.percentile(ttft, 50))
    assert m["served_share"]["value"] == 100.0
    end = data.t0 + SECONDS
    toks = sum(1 for s in data.done() for k in range(s.max_new_tokens)
               if data.landing(data.token_slot(s.req, k)) <= end)
    assert m["output_tok_s"]["value"] == toks / SECONDS > 0
    lm = harness.read_metrics(
        [{"name": k, "unit": "u"} for k in
         ("compile_s", "queue_wait_p50_ms", "slot_ms", "kv_pages_peak_share",
          "slot_device_ms", "step_mfu", "step_roofline", "idle_share")],
        "layer_metrics", data)
    # untraced: the device readers find nothing and stay out of the line
    assert set(lm) == {"compile_s", "queue_wait_p50_ms", "slot_ms",
                       "kv_pages_peak_share"}
    assert 0 < lm["kv_pages_peak_share"]["value"] <= 100
    verdict = harness.check(setup, data, 1)
    assert verdict["correct"], verdict


def test_int8_control_fails_the_limit(setup):
    """The reference in int8 in the program's place reads above a limit on
    some seed of three; the program reads under both on all three."""
    prog, ctrl = [], []
    for seed in (1, 2, 3):
        data, _ = _window(setup, seed)
        p, c = harness.check(setup, data, seed), harness.check(setup, data, seed, control=True)
        prog.append(p["correct"])
        ctrl.append(c["correct"])
    assert all(prog) and not all(ctrl)


@pytest.fixture
def planted(monkeypatch):
    """Swap a function of the timed path; retrace before and after."""
    from repro.runtime import engine as E

    def plant(name, fn):
        monkeypatch.setattr(E, name, fn)
        jax.clear_caches()

    yield E, plant
    monkeypatch.undo()
    jax.clear_caches()


def test_altered_token_is_caught(setup, planted):
    E, plant = planted
    greedy = E._sync_next
    V = setup.cell.spec["vocab_size"]
    plant("_sync_next", lambda sig, logits, samp, sync:
          (greedy(sig, logits, samp, sync) + 1) % V)
    data, _ = _window(setup, 2)
    verdict = harness.check(setup, data, 2)
    assert not verdict["correct"]
    assert verdict["checks"]["logit_gap_max"]["value"] > \
        verdict["checks"]["logit_gap_max"]["limit"]


def test_half_batch_left_out_is_caught(setup, planted):
    """A decode step that serves only the first half of its rows (the
    others get token 0) is caught by the comparison."""
    E, plant = planted
    greedy = E._sync_next

    def half(sig, logits, samp, sync):
        tok = greedy(sig, logits, samp, sync)
        return tok.at[tok.shape[0] // 2:].set(0)

    plant("_sync_next", half)
    data, _ = _window(setup, 4)
    verdict = harness.check(setup, data, 4)
    assert not verdict["correct"]
    assert verdict["checks"]["logit_gap_max"]["value"] > \
        verdict["checks"]["logit_gap_max"]["limit"]


def test_state_left_unchanged_is_caught(setup, planted):
    """A decode step that hands back its sync state unchanged never
    finishes a request: the run ends at the drain deadline, not correct."""
    E, plant = planted
    plant("_sync_step", lambda sync, nxt, sig: (sync, jnp.int32(0)))
    data, _ = _window(setup, 3, drain_s=2.0)
    verdict = harness.check(setup, data, 3)
    assert not verdict["correct"] and verdict["failed"] > 0
