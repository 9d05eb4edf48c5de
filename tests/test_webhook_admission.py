"""Out-of-process admission: WebhookConfiguration callouts.

Round-3 verdict item 2: the reference's admission boundary is a
standalone TLS server the apiserver calls out to
(`admission-webhook/main.go:443,447,597`), with registration + failure
semantics — not an in-process hook. These tests pin our equivalent: a
`WebhookConfiguration` CR makes the store POST objects to an external
HTTPS mutator before the in-lock admission phase, honoring
timeout/failurePolicy, keeping quota's check-then-insert atomic, and
running in the K8s order (mutating webhooks first, validating hooks
after — so quota meters the post-mutation object)."""

import pytest

from kubeflow_tpu.api.objects import new_resource
from kubeflow_tpu.controllers import quota
from kubeflow_tpu.controllers.webhook import (
    MutatingWebhookApp,
    make_webhook_config,
)
from kubeflow_tpu.testing import FakeApiServer
from kubeflow_tpu.testing.fake_apiserver import Invalid
from kubeflow_tpu.web.wsgi import serve


def _inject_env(obj, operation):
    for c in obj.spec.get("containers", []):
        env = c.setdefault("env", [])
        if not any(e["name"] == "INJECTED" for e in env):
            env.append({"name": "INJECTED", "value": operation})
    return obj


def _webhook(tls_paths, mutate=_inject_env, **cfg_kw):
    server, _ = serve(
        MutatingWebhookApp(mutate), host="127.0.0.1", port=0, tls=tls_paths
    )
    cfg = make_webhook_config(
        "test-webhook",
        f"https://127.0.0.1:{server.server_port}/mutate",
        tls_paths.ca_cert,
        **cfg_kw,
    )
    return server, cfg


def _pod(name="p", ns="default"):
    return new_resource(
        "Pod", name, ns, spec={"containers": [{"name": "w"}]}
    )


def test_webhook_mutates_on_create_and_update(tls_paths):
    api = FakeApiServer()
    server, cfg = _webhook(tls_paths)
    try:
        api.create(cfg)
        created = api.create(_pod()).thaw()
        env = created.spec["containers"][0]["env"]
        assert {"name": "INJECTED", "value": "CREATE"} in env
        created.spec["containers"][0]["env"] = []  # client strips it
        updated = api.update(created)
        env = updated.spec["containers"][0]["env"]
        assert {"name": "INJECTED", "value": "UPDATE"} in env
    finally:
        server.shutdown()


def test_webhook_denial_rejects_under_both_policies(tls_paths):
    def deny(obj, operation):
        raise Invalid("no pods today")

    for policy in ("Fail", "Ignore"):
        api = FakeApiServer()
        server, cfg = _webhook(tls_paths, mutate=deny,
                               failure_policy=policy)
        try:
            api.create(cfg)
            with pytest.raises(Invalid, match="no pods today"):
                api.create(_pod())
        finally:
            server.shutdown()


def test_webhook_down_fail_policy_rejects(tls_paths):
    api = FakeApiServer()
    server, cfg = _webhook(tls_paths, timeout_seconds=2)
    server.shutdown()  # the callee is gone before the first callout
    api.create(cfg)
    with pytest.raises(Invalid, match="failurePolicy=Fail"):
        api.create(_pod())


def test_webhook_down_ignore_policy_admits_unmodified(tls_paths):
    api = FakeApiServer()
    server, cfg = _webhook(
        tls_paths, failure_policy="Ignore", timeout_seconds=2
    )
    server.shutdown()
    api.create(cfg)
    created = api.create(_pod())
    assert "env" not in created.spec["containers"][0]


def test_kinds_filter_scopes_callouts(tls_paths):
    api = FakeApiServer()
    server, cfg = _webhook(tls_paths)  # kinds=("Pod",)
    try:
        api.create(cfg)
        cm = api.create(new_resource("ConfigMap", "c", spec={"k": "v"}))
        assert cm.spec == {"k": "v"}  # untouched: not a webhook kind
    finally:
        server.shutdown()


def test_webhook_config_validation():
    api = FakeApiServer()
    with pytest.raises(Invalid, match="https"):
        api.create(new_resource(
            "WebhookConfiguration", "plain", "",
            spec={"url": "http://x/mutate", "kinds": ["Pod"]},
        ))
    with pytest.raises(Invalid, match="failurePolicy"):
        api.create(new_resource(
            "WebhookConfiguration", "badpol", "",
            spec={"url": "https://x/mutate", "kinds": ["Pod"],
                  "failurePolicy": "Maybe"},
        ))
    with pytest.raises(Invalid, match="kinds"):
        api.create(new_resource(
            "WebhookConfiguration", "nokinds", "",
            spec={"url": "https://x/mutate"},
        ))
    # A webhook admitting WebhookConfigurations would brick the store.
    with pytest.raises(Invalid, match="self-bricking"):
        api.create(new_resource(
            "WebhookConfiguration", "loop", "",
            spec={"url": "https://x/mutate",
                  "kinds": ["WebhookConfiguration"]},
        ))


def test_mutating_webhook_runs_before_quota(tls_paths):
    """K8s admission order: the validating phase judges the
    POST-mutation object — a webhook-injected chip ask is metered."""

    def inject_chips(obj, operation):
        obj.spec["containers"][0]["resources"] = {
            "limits": {"google.com/tpu": 4}
        }
        return obj

    api = FakeApiServer()
    quota.register(api)
    api.create(new_resource(
        "ResourceQuota", "kf-resource-quota", "default",
        spec={"hard": {"google.com/tpu": 0}},
    ))
    server, cfg = _webhook(tls_paths, mutate=inject_chips)
    try:
        api.create(cfg)
        with pytest.raises(quota.QuotaExceeded):
            api.create(_pod())
    finally:
        server.shutdown()


def test_callout_does_not_hold_the_store_lock(tls_paths):
    """The webhook round trip must never stall other writers: while one
    create is parked inside the callout, an unrelated write completes."""
    import threading
    import time

    api = FakeApiServer()
    release = threading.Event()

    def slow(obj, operation):
        release.wait(10)
        return obj

    server, cfg = _webhook(tls_paths, mutate=slow, timeout_seconds=15)
    try:
        api.create(cfg)
        t = threading.Thread(target=lambda: api.create(_pod()), daemon=True)
        t.start()
        time.sleep(0.3)  # the pod create is now parked in the callout
        t0 = time.monotonic()
        api.create(new_resource("ConfigMap", "free", spec={}))
        assert time.monotonic() - t0 < 1.0, (
            "an unrelated write waited on a webhook round trip"
        )
        release.set()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        release.set()
        server.shutdown()


def test_durable_store_persists_post_mutation_object(tls_paths, tmp_path):
    """The WAL records what was actually stored: the mutated object."""
    api = FakeApiServer(persist_dir=str(tmp_path / "state"))
    server, cfg = _webhook(tls_paths)
    try:
        api.create(cfg)
        api.create(_pod())
    finally:
        server.shutdown()
    del api
    restored = FakeApiServer(persist_dir=str(tmp_path / "state"))
    env = restored.get("Pod", "p").spec["containers"][0]["env"]
    assert {"name": "INJECTED", "value": "CREATE"} in env


def test_webhook_cannot_alter_immutable_fields(tls_paths):
    """A mutator only gets spec/labels/annotations: identity and
    concurrency fields are immutable (a dropped resourceVersion would
    disable the stale-write Conflict check; a swapped kind would bypass
    per-kind validation that ran before the callout)."""

    def swap_identity(obj, operation):
        obj.metadata.name = "evil"
        return obj

    api = FakeApiServer()
    server, cfg = _webhook(tls_paths, mutate=swap_identity)
    try:
        api.create(cfg)
        with pytest.raises(Invalid, match="immutable"):
            api.create(_pod())
    finally:
        server.shutdown()


def test_bad_timeout_rejected_at_config_time():
    api = FakeApiServer()
    for bad in ("5s", -1, 0, True):
        with pytest.raises(Invalid, match="timeoutSeconds"):
            api.create(new_resource(
                "WebhookConfiguration", "badtimeout", "",
                spec={"url": "https://x/mutate", "kinds": ["Pod"],
                      "timeoutSeconds": bad},
            ))


def test_changed_apply_pays_one_callout(tls_paths):
    """apply() on a changed object runs each webhook ONCE (the no-op
    comparison's mutation is reused), and no-op applies don't re-store."""
    calls = []

    def counting(obj, operation):
        calls.append(operation)
        return _inject_env(obj, operation)

    api = FakeApiServer()
    server, cfg = _webhook(tls_paths, mutate=counting)
    try:
        api.create(cfg)
        api.create(_pod())
        calls.clear()
        changed = _pod()
        changed.spec["containers"][0]["image"] = "v2"
        api.apply(changed)
        assert calls == ["UPDATE"], calls  # one round trip, not two
        calls.clear()
        rv = api.get("Pod", "p").metadata.resource_version
        api.apply(changed)  # identical desired state: no-op
        assert api.get("Pod", "p").metadata.resource_version == rv
        assert calls == ["UPDATE"], calls  # only the comparison callout
    finally:
        server.shutdown()


def test_native_backend_refuses_webhook_configs():
    pytest.importorskip("kubeflow_tpu.native.core")
    from kubeflow_tpu.native.apiserver import NativeApiServer

    api = NativeApiServer()
    with pytest.raises(Invalid, match="native store backend"):
        api.create(new_resource(
            "WebhookConfiguration", "x", "",
            spec={"url": "https://x/mutate", "kinds": ["Pod"]},
        ))


def test_wildcard_edit_cannot_register_webhooks():
    """Registering a webhook = code execution on every future write of
    the kinds it names — the same escalation class as RBAC objects, so
    `resources: ["*"]` must not reach webhookconfigurations either."""
    from kubeflow_tpu.api.rbac import (
        make_cluster_role_binding,
        seed_cluster_roles,
        subject_access_review,
    )

    api = FakeApiServer()
    seed_cluster_roles(api)
    api.create(
        make_cluster_role_binding("ed", "kubeflow-edit", "mallory@x.co")
    )
    assert subject_access_review(api, "mallory@x.co", "create", "pods", "")
    assert not subject_access_review(
        api, "mallory@x.co", "create", "webhookconfigurations", ""
    )
    # cluster-admin's explicit grant still reaches them.
    api.create(
        make_cluster_role_binding("adm", "kubeflow-admin", "root@x.co")
    )
    assert subject_access_review(
        api, "root@x.co", "create", "webhookconfigurations", ""
    )


def test_webhook_cannot_forge_status(tls_paths):
    """The facade strips status from clients without the status grant
    BEFORE admission runs; a webhook adding status afterwards would
    bypass that forgery guard — status is immutable through callouts."""

    def forge(obj, operation):
        obj.status = {"phase": "Succeeded"}
        return obj

    api = FakeApiServer()
    server, cfg = _webhook(tls_paths, mutate=forge)
    try:
        api.create(cfg)
        with pytest.raises(Invalid, match="immutable"):
            api.create(_pod())
    finally:
        server.shutdown()


def test_webhook_config_survives_durable_restart(tls_paths, tmp_path):
    """A restored store keeps calling out: the WebhookConfiguration is a
    CR like any other, and the restore path rebuilds the webhook index
    (an unindexed config would silently fail open after restart)."""
    api = FakeApiServer(persist_dir=str(tmp_path / "state"))
    server, cfg = _webhook(tls_paths)
    try:
        api.create(cfg)
        api.close()
        restored = FakeApiServer(persist_dir=str(tmp_path / "state"))
        created = restored.create(_pod())
        env = created.spec["containers"][0]["env"]
        assert {"name": "INJECTED", "value": "CREATE"} in env
    finally:
        server.shutdown()


def test_namespace_and_object_selectors_scope_callouts(tls_paths):
    """The namespaceSelector/objectSelector analogs: a scoped webhook
    only sees objects in its namespaces AND matching its labels —
    everything else is admitted without a round trip."""
    api = FakeApiServer()
    server, cfg = _webhook(
        tls_paths,
        namespaces=("team-a",),
        match_labels={"inject": "yes"},
    )
    try:
        api.create(cfg)
        hit = api.create(new_resource(
            "Pod", "hit", "team-a",
            spec={"containers": [{"name": "w"}]},
            labels={"inject": "yes"},
        ))
        assert "env" in hit.spec["containers"][0]
        wrong_ns = api.create(new_resource(
            "Pod", "wrong-ns", "team-b",
            spec={"containers": [{"name": "w"}]},
            labels={"inject": "yes"},
        ))
        assert "env" not in wrong_ns.spec["containers"][0]
        wrong_labels = api.create(new_resource(
            "Pod", "wrong-labels", "team-a",
            spec={"containers": [{"name": "w"}]},
        ))
        assert "env" not in wrong_labels.spec["containers"][0]
    finally:
        server.shutdown()


def test_webhook_config_embeds_inline_pem(tls_paths):
    """ADVICE r4: caBundle must be self-contained PEM (the K8s caBundle
    form) — a path in the CR would make the apiserver open arbitrary
    local files chosen by whoever can create webhookconfigurations, and
    would break remote clients whose CA path doesn't exist server-side.
    make_webhook_config inlines a readable path at build time; the store
    verifies the callout against that embedded PEM."""
    api = FakeApiServer()
    server, cfg = _webhook(tls_paths)
    try:
        assert "-----BEGIN CERTIFICATE-----" in cfg.spec["caBundle"]
        api.create(cfg)
        created = api.create(_pod())
        env = created.spec["containers"][0]["env"]
        assert {"name": "INJECTED", "value": "CREATE"} in env
    finally:
        server.shutdown()


def test_webhook_config_with_path_cabundle_is_rejected(tls_paths):
    """The STORE enforces inline PEM: a path-form caBundle posted
    directly (bypassing make_webhook_config) would otherwise make the
    apiserver open an attacker-chosen local file on every callout."""
    api = FakeApiServer()
    cfg = make_webhook_config(
        "path-webhook", "https://127.0.0.1:1/mutate", tls_paths.ca_cert
    )
    cfg.spec["caBundle"] = tls_paths.ca_cert  # raw path, as a raw POST
    with pytest.raises(Invalid, match="inline PEM"):
        api.create(cfg)
    with pytest.raises(ValueError, match="neither PEM"):
        make_webhook_config(
            "typo-webhook", "https://127.0.0.1:1/mutate", "/nope/ca.crt"
        )
