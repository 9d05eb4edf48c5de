"""The trace reduction, on hand-made events and on a trace recorded on the
chip (`data/trace_two_steps.json`: two executions of the train step)."""

import json
import pathlib

import pytest

from benchmarks.lib import loader, trace

DATA = pathlib.Path(__file__).parent / "data"


def test_op_name_is_what_stands_before_the_equals_sign():
    name, text = trace.op_name(
        "%flash_fwd_compact.8 = (bf16[128,2048,128]{2,1,0}) custom-call(s32[3] %x)"
    )
    assert name == "flash_fwd_compact.8" and text.startswith("(bf16[")
    assert trace.op_name("plain") == ("plain", "")
    assert trace.module_name("jit_train_step(3258227417418557109)") == "jit_train_step"


def test_busy_is_a_union_and_gaps_take_the_span_that_covers_them():
    events = {
        "devices": {0: {
            "ops": [["a", 0, 10], ["b", 5, 10], ["a", 30, 10], ["c", 100, 20]],
            "modules": [["m(1)", 0, 40], ["feed(2)", 50, 5], ["m(1)", 100, 20]],
        }},
        "spans": [["input", 41, 58], ["dispatch", 16, 13]],
    }
    r = trace.reduce(events)
    assert r.window_ns == (0, 120)
    assert r.busy_ns == {0: 15 + 10 + 20}
    assert r.op_time_ns[0] == {"a": 20, "b": 10, "c": 20}
    assert r.op_count[0] == {"a": 2, "b": 1, "c": 1}
    assert r.module_runs_ns[0] == {"m": [40, 20], "feed": [5]}
    assert r.main_module() == "m"
    assert r.between_runs_ns() == [60]
    # gaps: 40..100 (input covers 41..99) and 15..30 (dispatch covers 16..29)
    assert r.idle_gaps == [("input", 60), ("dispatch", 15)]
    assert r.idle_share() == pytest.approx(1 - 45 / 120)
    assert r.busy_s == pytest.approx(45e-9)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce({"devices": {}, "spans": []})


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(json.loads((DATA / "trace_two_steps.json").read_text()))


def test_recorded_trace_counts_and_times(recorded):
    r = recorded
    assert {k: len(v) for k, v in r.module_runs_ns[0].items()} == {
        "jit_train_step": 2, "jit_convert_element_type": 1, "jit__lambda": 1,
    }
    # six layers: a forward and a fused backward kernel each, per step
    count = lambda prefix: sum(
        c for n, c in r.op_count[0].items() if n.startswith(prefix)
    )
    assert count("flash_fwd_compact") == 12
    assert count("flash_bwd_fused") == 12
    assert count("flash_delta") == 12
    assert r.time_by_prefix("flash_") == 68835370
    assert r.busy_ns[0] == 796549036
    assert r.window_ns == (7127, 796591733)
    assert r.between_runs_ns() == [22238]
    assert r.idle_gaps[0] == ("input", 11631)
    # the breakdown: kinds of operation first, then single operations
    top = r.top_ops(10)
    assert [t[0] for t in top[:3]] == [
        "all fusion", "all flash_bwd_fused", "all flash_fwd_compact",
    ]
    assert top[7][0].startswith("fusion.20 (f32[50304,2048]")
    assert len(top) == 10 and all(t[1] > 0 for t in top)


def test_metric_readers_on_the_recorded_trace(recorded):
    cell = {
        "workload": {"batch": 8, "seq_len": 2048, "mesh": {}},
        "facts": {
            "device_kind": "TPU v5 lite", "flops_per_token": 3.185e9,
            "tokens_per_s_per_chip": 41000.0,
            "numbers": {"num_attention_heads": 16, "head_dim": 128},
        },
    }
    read = lambda name: loader.load_metric(name).read(recorded, [], cell)
    assert read("flash_time_pct.train") == pytest.approx(8.64, abs=0.01)
    # forward 2.18 ms against 0.70 ms of needed matmuls, backward 3.35 ms
    # against 1.40 ms: between the two
    assert read("flash_roofline.train") == pytest.approx(39.3, abs=0.2)
    assert read("device_idle_pct.train") == pytest.approx(0.0045, abs=0.001)
    assert read("input_wait_ms.train") == pytest.approx(0.022238)
    assert read("mfu_pct.train") == pytest.approx(66.29, abs=0.01)


def test_spans_are_read_from_a_profile_written_here(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:input"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    events = trace.load_events(trace.find_xplane(str(tmp_path)))
    assert [s[0] for s in events["spans"]] == ["input"]
    assert events["devices"] == {}  # no TPU plane in a CPU profile
    assert any(p.startswith("/host:") for p in events["layout"])
