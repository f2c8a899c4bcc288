"""The prefill's operations, bytes and model FLOPs against hand counts at
qwen2.5-14b's shapes, and the prefill metric readers on a recorded window
made up here (a device trace summary and the engine's step records)."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from serve_bench_util import BENCH

import costs
import harness
import prefill_costs
import trace_reduce
from repro.serving import tracing

with open(os.path.join(BENCH, "configs", "qwen2.5-14b-d8.json")) as f:
    CFG = json.load(f)

# One layer's projection weights at the 14b's widths: q and o 5120 x 5120,
# k and v 1024 x 5120, gate, up and down 13824 x 5120.
LAYER_MATS = 2 * 5120 * 5120 + 2 * 1024 * 5120 + 3 * 13824 * 5120
HEAD = 2 * 152064 * 5120                 # the untied head, one position
PAIR = 4 * 128 * 40 * 8                  # QK^T and PV of one (query, key) pair, all layers
ROW_BYTES = 2 * 8 * 128 * (2 * 40 + 2 * 8)   # Q, K, V, O of one token, all layers, bf16


def test_attention_cost_hand_counts():
    assert LAYER_MATS == 275_251_200
    ops, byts = prefill_costs.attention_cost(CFG, [2048])
    assert ops == PAIR * 2048 * 2049 // 2 == 343_765_155_840
    assert byts == ROW_BYTES * 2048 == 402_653_184
    ops2, byts2 = prefill_costs.attention_cost(CFG, [2048, 1024])
    assert ops2 == PAIR * (2048 * 2049 // 2 + 1024 * 1025 // 2)
    assert byts2 == ROW_BYTES * 3072
    # Causal work grows with the square of the length; bytes with the length.
    assert prefill_costs.attention_cost(CFG, [4096])[0] > 3.99 * ops


def test_prefill_flops_hand_counts_head_on_the_last_position_only():
    one = prefill_costs.prefill_flops(CFG, [2048])
    assert one == 2 * 8 * LAYER_MATS * 2048 + PAIR * 2048 * 2049 // 2 + HEAD
    two = prefill_costs.prefill_flops(CFG, [2048, 1024])
    assert two == (2 * 8 * LAYER_MATS * 3072
                   + PAIR * (2048 * 2049 // 2 + 1024 * 1025 // 2) + 2 * HEAD)
    # The last position is one generated token's worth (costs.py), the
    # earlier ones carry no head.
    earlier = sum(2 * 8 * LAYER_MATS + PAIR * p for p in range(1, 2048))
    assert one == earlier + costs.model_flops_per_token(CFG, 2048)


def _request(uid, prompt_len, times):
    r = harness.traffic_lib.Req(uid=uid, prompt_len=prompt_len, out_len=len(times), due=0.0)
    r.token_times = list(times)
    return r


def _run(requests, **kw):
    base = dict(cfg=CFG, requests=requests, window_t0=10.0, window_t1=20.0,
                calls=[], steps=[], summary=None,
                device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    base.update(kw)
    run = SimpleNamespace(**base)
    run.in_window = lambda t: t is not None and run.window_t0 <= t <= run.window_t1
    run.window_s = run.window_t1 - run.window_t0
    return run


def test_window_flops_counts_a_prompt_once_and_each_later_token_at_its_context():
    # Request 0 was admitted before the window: only its tokens 2 and 3 count.
    # Request 1 was admitted in it: its prefill, then its token 1.
    run = _run([_request(0, 1500, [5.0, 9.0, 11.0, 12.0]), _request(1, 2048, [13.0, 14.0])])
    want = (costs.model_flops_per_token(CFG, 1502) + costs.model_flops_per_token(CFG, 1503)
            + prefill_costs.prefill_flops(CFG, [2048]) + costs.model_flops_per_token(CFG, 2049))
    assert prefill_costs.window_flops(run) == pytest.approx(want, rel=1e-12)


def _metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def recorded():
    """A window of three steps on a made-up clock far from any live
    engine's: step 0 prefills requests 7 and 8 (2048 and 1024 tokens,
    padded to 2 x 2048), step 1 decodes, step 2 prefills request 9 (3000
    tokens, padded to 4096); steps 1 and 2 are traced."""
    t0 = -1e9
    clock = iter([t0 + x for x in (0.1, 0.9, 1.1, 1.9, 2.1, 2.9)]).__next__
    tracer = tracing.Tracer(clock)
    admitted = [[7, 8], [], [9]]
    counts = [(3072, 4096), (0, 0), (3000, 4096)]
    for i, (uids, (real, padded)) in enumerate(zip(admitted, counts)):
        with tracer.step(i, 8):
            for u in uids:
                tracer.note("admitted", u)
            tracer.add("prefill_tokens", real)
            tracer.add("prefill_padded_tokens", padded)
    steps = [harness.Step(t0=t0 + i, t1=t0 + i + 1, admitted=len(a))
             for i, a in enumerate(admitted)]
    calls = [harness.Call("prefill", 0, False, rows=3072, seqs=2),
             harness.Call("decode", 1, True, rows=3),
             harness.Call("prefill", 2, True, rows=3000, seqs=1)]
    summary = trace_reduce.TraceSummary(
        window_s=2.0, busy_s=1.5, devices=1,
        op_s={}, module_op_s={("jit_prefill", "fused_pack_mmt4d"): 0.30,
                              ("jit_prefill", "flash_prefill_attention"): 0.04,
                              ("jit_prefill", "fusion"): 0.06,
                              ("jit_prefill_cache", "broadcast"): 0.01,
                              ("jit_decode", "fused_gemv"): 0.02},
        module_runs={}, idle_by_host={}, planes={})
    reqs = [_request(7, 2048, [t0 + 0.9]), _request(8, 1024, [t0 + 0.9]),
            _request(9, 3000, [t0 + 2.9])]
    run = _run(reqs, window_t0=t0, window_t1=t0 + 3.0, steps=steps, calls=calls,
               summary=summary)
    yield run
    del tracer


def test_prefill_readers_on_a_recorded_window(recorded):
    run = recorded
    pk = costs.peaks("TPU v5 lite")
    # The traced prefill call of step 2: 3000 real tokens, 0.40 s of jit_prefill.
    assert _metric("prefill_ms_per_ktok").read(run) == pytest.approx(1e6 * 0.40 / 3000)
    assert prefill_costs.traced_prefill_lengths(run) == [[3000]]
    least = costs.least_time(*prefill_costs.attention_cost(CFG, [3000]), pk)[0]
    assert _metric("flash_prefill_attention_roofline").read(run) == pytest.approx(
        100 * least / 0.04)
    ops, byts = costs.projection_cost(CFG, 3000)
    o, b = costs.head_cost(CFG, 1)
    assert _metric("prefill_matmul_roofline").read(run) == pytest.approx(
        100 * costs.least_time(ops + o, byts + b, pk)[0] / 0.30)
    # Every step of the window counts, traced or not.
    assert _metric("prefill_pad_frac").read(run) == pytest.approx(1 - 6072 / 8192)
    assert _metric("device_idle.longdoc").read(run) == pytest.approx(0.25)
    flops = sum(prefill_costs.prefill_flops(CFG, [n]) for n in (2048, 1024, 3000))
    assert _metric("mfu.longdoc").read(run) == pytest.approx(100 * flops / 3.0 / 197e12)


def test_prefill_readers_read_nothing_without_a_device_trace_or_counter(recorded, monkeypatch):
    run = recorded
    run.summary = trace_reduce.TraceSummary(2.0, 0.0, 0, {}, {}, {}, {}, {})
    for name in ("prefill_ms_per_ktok", "prefill_matmul_roofline",
                 "flash_prefill_attention_roofline", "device_idle.longdoc"):
        assert _metric(name).read(run) is None, name
    run.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert _metric("mfu.longdoc").read(run) is None
    # A program whose step records have no padded-token counter, and a
    # window with no step records.
    pad = _metric("prefill_pad_frac")
    monkeypatch.setattr(pad, "window_records", lambda run: [SimpleNamespace(prefill_tokens=9)])
    assert pad.read(run) is None
    monkeypatch.setattr(pad, "window_records", lambda run: None)
    assert pad.read(run) is None
