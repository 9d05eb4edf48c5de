"""Control-plane tracing spans (the reference had none — SURVEY.md §5)."""

import threading

import pytest

from kubeflow_tpu.api.objects import new_resource
from kubeflow_tpu.utils import tracing
from kubeflow_tpu.utils.tracing import HEADER, Tracer


def test_span_records_timing_and_attributes():
    t = Tracer()
    with t.span("work", component="test") as span:
        assert span.trace_id and span.span_id
    (rec,) = t.export()
    assert rec["name"] == "work"
    assert rec["attributes"]["component"] == "test"
    assert rec["durationMs"] >= 0
    assert rec["error"] is None
    assert t.export() == []  # drained


def test_nested_spans_share_trace_and_link_parent():
    t = Tracer()
    with t.span("outer") as outer:
        with t.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    inner_rec, outer_rec = t.export()  # inner finishes first
    assert inner_rec["parentId"] == outer_rec["spanId"]


def test_error_flag_set_and_exception_propagates():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    (rec,) = t.export()
    assert "ValueError" in rec["error"]


def test_ring_buffer_drops_oldest():
    t = Tracer(capacity=2)
    for i in range(4):
        with t.span(f"s{i}"):
            pass
    out = t.export()
    assert [r["name"] for r in out] == ["s2", "s3"]
    assert t.dropped == 2


def test_threads_do_not_share_span_context():
    t = Tracer()
    seen = {}

    def worker(name):
        with t.span(name) as s:
            seen[name] = s.parent_id

    with t.span("main"):
        th = threading.Thread(target=worker, args=("child-thread",))
        th.start()
        th.join()
    # A fresh thread has no inherited context -> new root span.
    assert seen["child-thread"] is None


def test_header_roundtrip():
    t = tracing.tracer
    with t.span("req"):
        hdr = tracing.trace_header()
        assert HEADER in hdr
        assert tracing.from_header(hdr) == tracing.current_trace_id()
    t.export()
    assert tracing.trace_header() == {}  # no active span


def test_reconcile_spans_emitted():
    from kubeflow_tpu.controllers.notebook import NotebookController
    from kubeflow_tpu.testing.fake_apiserver import FakeApiServer

    tracing.tracer.export()  # drain whatever other tests left
    api = FakeApiServer()
    ctl = NotebookController(api)
    api.create(new_resource("Notebook", "nb", "team", spec={"image": "i"}))
    ctl.controller.run_until_idle()
    spans = [
        s for s in tracing.tracer.export()
        if s["name"] == "reconcile"
        and s["attributes"].get("controller") == "notebook-controller"
    ]
    assert spans
    assert spans[0]["attributes"]["key"] == "team/nb"


def test_http_spans_with_propagation():
    from kubeflow_tpu.testing.fake_apiserver import FakeApiServer
    from kubeflow_tpu.testing.apiserver_http import ApiServerApp
    from kubeflow_tpu.web import TestClient

    tracing.tracer.export()
    client = TestClient(ApiServerApp(FakeApiServer()))
    resp = client.get("/apis/Notebook", headers={HEADER: "abc123"})
    assert resp.status == 200
    spans = [
        s for s in tracing.tracer.export() if s["name"] == "http"
    ]
    assert spans
    assert spans[-1]["traceId"] == "abc123"  # caller's trace continued
    assert spans[-1]["attributes"]["status"] == 200
    assert spans[-1]["attributes"]["path"] == "/apis/Notebook"


def test_debug_traces_endpoint_drains():
    from kubeflow_tpu.testing.fake_apiserver import FakeApiServer
    from kubeflow_tpu.testing.apiserver_http import ApiServerApp
    from kubeflow_tpu.web import TestClient

    tracing.tracer.export()
    client = TestClient(ApiServerApp(FakeApiServer()))
    client.get("/apis/Notebook")
    body = client.get("/debug/traces").json()
    assert any(s["name"] == "http" for s in body["spans"])
    # Drained: only the /debug/traces request's own span remains next time.
    again = client.get("/debug/traces").json()
    assert all(
        s["attributes"].get("path") == "/debug/traces"
        for s in again["spans"]
    )


def test_http_client_propagates_active_trace():
    """A span active in the caller (e.g. a reconcile) must continue into
    the apiserver's http span through HttpApiClient."""
    from kubeflow_tpu.testing.fake_apiserver import FakeApiServer
    from kubeflow_tpu.testing.apiserver_http import ApiServerApp, HttpApiClient
    from kubeflow_tpu.web.wsgi import serve

    tracing.tracer.export()
    api = FakeApiServer()
    server, _ = serve(ApiServerApp(api), host="127.0.0.1", port=0)
    try:
        client = HttpApiClient(f"http://127.0.0.1:{server.server_port}")
        with tracing.tracer.span("caller") as outer:
            client.list("Notebook")
            want = outer.trace_id
    finally:
        server.shutdown()
    # Membership, not last-element: an in-flight long-poll from a prior
    # test's daemon watch thread may drop a stray http span on the global
    # tracer while this test runs.
    http = [s for s in tracing.tracer.export() if s["name"] == "http"]
    assert want in {s["traceId"] for s in http}


# -- the span primitive on the profiler's clock (ISSUE 26) -------------------


def test_durations_are_monotonic_under_a_stepped_wall_clock(monkeypatch):
    """The wall clock may step (NTP, a suspended VM); start, end and the
    duration come from `perf_counter_ns` and cannot go backwards."""
    wall = iter([1000.0, 400.0, 300.0])  # steps back between reads
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    t = Tracer()
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.export()
    for rec in (inner, outer):
        assert rec["endNs"] >= rec["startNs"]
        assert rec["durationMs"] == (rec["endNs"] - rec["startNs"]) / 1e6
        assert rec["end"] >= rec["start"]  # start + monotonic duration
    assert outer["start"] == 1000.0 and inner["start"] == 400.0
    assert outer["startNs"] <= inner["startNs"] <= inner["endNs"] <= outer["endNs"]


def test_ids_are_unique_across_threads():
    t = Tracer(capacity=20000)
    barrier = threading.Barrier(8)

    def worker():
        barrier.wait()
        for _ in range(1000):
            with t.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
        assert not th.is_alive()
    spans = t.export()
    assert len(spans) == 8000
    ids = [s["spanId"] for s in spans] + [s["traceId"] for s in spans]
    assert len(set(ids)) == 16000
    assert all(len(i) == 16 for i in ids)


def _profile_events(logdir) -> dict[str, list[dict]]:
    """Host-plane events of the one profile under `logdir`, by name, each
    with its stats."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    out: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append({
                    "start_ns": ev.start_ns, "duration_ns": ev.duration_ns,
                    "stats": dict(ev.stats),
                })
    return out


def test_span_lands_in_a_profile_taken_meanwhile(tmp_path):
    """In a process that has imported jax a span is also a TraceAnnotation:
    the same span is in the ring and, with its attributes, on the host
    plane of the profile — with no flag set anywhere."""
    import jax
    import jax.numpy as jnp

    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("unit.step", step_num=7):
            with t.span("unit.child", what="sum"):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with t.span("unit.after"):  # no profile running: the ring only
        pass
    events = _profile_events(tmp_path)
    (step,), (child,) = events["unit.step"], events["unit.child"]
    assert int(step["stats"]["step_num"]) == 7
    assert child["stats"]["what"] == "sum"
    assert step["start_ns"] <= child["start_ns"]
    assert (child["start_ns"] + child["duration_ns"]
            <= step["start_ns"] + step["duration_ns"])
    assert "unit.after" not in events
    ring = {s["name"]: s for s in t.export()}
    assert set(ring) == {"unit.step", "unit.child", "unit.after"}
    # the annotation is entered before the ring's stamp and left after it
    assert ring["unit.child"]["durationMs"] * 1e6 <= child["duration_ns"] + 1


def test_a_process_without_jax_imports_none_through_tracing():
    """A controller or web process pays nothing new: `utils/tracing` never
    imports jax, and a span there writes no annotation."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from kubeflow_tpu.utils import tracing\n"
        "with tracing.tracer.span('work', k=1):\n"
        "    pass\n"
        "(rec,) = tracing.tracer.export()\n"
        "assert rec['name'] == 'work' and rec['durationMs'] >= 0\n"
        "assert not [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))], 'jax was imported'\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"
