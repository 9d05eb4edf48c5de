"""Model-server binary.

    python -m kubeflow_tpu.serving --model name=<ckpt_dir> ... [--port 8500]

Each --model loads an orbax checkpoint written by the training loop and
serves it at /v1/models/<name>. With no --model flags a demo model is
served under the name "demo" so the REST surface can be probed standalone
(the tf-serving sample served mnist the same way). The :predict route
speaks both JSON and the binary tensor protocol
(``application/x-kftpu-tensor``, `serving/wire.py`) — router-side
`HttpReplica` clients negotiate binary automatically.

Replica mode (the ServingDeployment data plane, docs/serving.md):

    python -m kubeflow_tpu.serving --apiserver URL[,URL...] \
        --replica <name> [--namespace ns]

The worker joins the fleet the serving controller materialized: it reads
its own ``ServingReplica`` object for config (model, batching knobs,
modelVersion — the PR 2 watch machinery is the push channel), loads the
servable, stamps ``status.ready`` + its endpoint + queue stats, and hot
swaps the model whenever the controller bumps ``spec.modelVersion``
(repository.load makes the new version latest; the server's predictor
swaps batching queues off the request path). The apiserver address is a
comma-separated endpoint list (`endpoints_from_env`) — a worker spawned
against one facade today transparently gains failover the day its env
grows a second endpoint.
"""

from __future__ import annotations

import argparse
import logging
import os
import threading

from kubeflow_tpu.utils import threads

log = logging.getLogger(__name__)

REPLICA_KIND = "ServingReplica"


def build_servable_from_rspec(rspec: dict, *, device=None):
    """Materialize the replica spec's model: an orbax checkpoint when
    `checkpointDir` is set (version = checkpoint step), else the demo
    model at the spec's modelVersion."""
    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import resnet50, tiny_resnet
    from kubeflow_tpu.serving.servable import Servable

    name = rspec.get("model", "demo")
    max_batch = int(rspec.get("maxBatch", 64))
    ckpt_dir = rspec.get("checkpointDir") or ""
    if ckpt_dir:
        return Servable.from_checkpoint(
            name,
            resnet50(),
            ckpt_dir,
            np.zeros((1, 224, 224, 3), np.float32),
            max_batch=max_batch,
            train=False,
        )
    module = tiny_resnet(num_classes=10)
    variables = jax.jit(module.init)(
        jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32)
    )
    return Servable.from_module(
        name, module, variables,
        version=int(rspec.get("modelVersion") or 1),
        max_batch=max_batch,
        warmup_example=np.zeros((32, 32, 3), np.float32),
        device=device,
        train=False,
    )


def sync_replica_once(
    api,
    name: str,
    namespace: str,
    repository,
    *,
    build_servable,
    endpoint: str = "",
    queue_stats=None,
) -> int | None:
    """One reconcile of worker state against the ServingReplica object:
    load the spec'd model version if it isn't serving yet, then stamp
    status (ready/version/endpoint/queue signal). Returns the live
    version, or None when the object is gone (deployment deleted — the
    caller shuts down). Idempotent and crash-safe: all state lives in
    the object and the repository."""
    from kubeflow_tpu.testing.fake_apiserver import Conflict, NotFound

    try:
        replica = api.get(REPLICA_KIND, name, namespace)
    except NotFound:
        return None
    rspec = dict(replica.spec)
    model_specs = rspec.get("models") or []
    model_rows: dict[str, int] = {}
    if model_specs:
        # Multiplexed fleet: one worker serves every spec'd model.
        # Unlike the in-process MultiModelReplica there is no paging
        # here — a worker owns its whole address space, so everything
        # it loads stays resident; LRU paging is the router-side
        # replica's concern.
        from kubeflow_tpu.serving.replica import LocalReplicaRuntime

        live = 0
        for mspec in model_specs:
            mr = LocalReplicaRuntime.model_rspec(rspec, mspec)
            mname = mr["model"]
            want = int(mr.get("modelVersion") or 0)
            try:
                mlive = repository.get(mname).version
            except Exception:
                mlive = None
            if mlive is None or (want and mlive != want):
                servable = build_servable(mr)
                repository.load(servable)
                mlive = servable.version
                log.info(
                    "replica %s: serving %s version %s", name, mname, mlive
                )
            model_rows[mname] = mlive
            live = max(live, mlive)
    else:
        model = rspec.get("model", "demo")
        want_version = int(rspec.get("modelVersion") or 0)
        try:
            live = repository.get(model).version
        except Exception:
            live = None
        if live is None or (want_version and live != want_version):
            servable = build_servable(rspec)
            repository.load(servable)
            live = servable.version
            log.info(
                "replica %s: serving %s version %s", name, model, live
            )
    status = {
        "ready": True,
        "version": live,
        "endpoint": endpoint,
        "pid": os.getpid(),
    }
    if model_rows:
        status["models"] = model_rows
    if queue_stats is not None:
        stats = queue_stats()
        status["queueDepth"] = int(stats.get("queue_depth") or 0)
        status["inflight"] = int(stats.get("inflight") or 0)
    try:
        fresh = api.get(REPLICA_KIND, name, namespace).thaw()
        new_status = dict(fresh.status)
        new_status.update(status)
        if new_status != fresh.status:
            fresh.status = new_status
            api.update_status(fresh)
    except (NotFound, Conflict):
        pass  # next heartbeat retries against fresh state
    return live


def run_replica(
    api,
    name: str,
    namespace: str,
    repository,
    *,
    build_servable,
    endpoint: str = "",
    queue_stats=None,
    heartbeat_s: float = 1.0,
    stop: threading.Event | None = None,
) -> None:
    """Worker loop: sync once, then re-sync on every watch event touching
    our object (config push — no polling for spec changes) plus a slow
    heartbeat that keeps the status queue signal fresh."""
    stop = stop or threading.Event()
    dirty = threading.Event()

    def on_event(event: str, obj) -> None:
        if (
            obj.metadata.name == name
            and obj.metadata.namespace == namespace
        ):
            dirty.set()

    api.watch(on_event, REPLICA_KIND)
    while not stop.is_set():
        dirty.clear()
        live = sync_replica_once(
            api, name, namespace, repository,
            build_servable=build_servable,
            endpoint=endpoint,
            queue_stats=queue_stats,
        )
        if live is None:
            log.info("replica %s: object gone; shutting down", name)
            return
        dirty.wait(heartbeat_s)


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser(prog="kubeflow-tpu-model-server")
    parser.add_argument("--host", default="0.0.0.0")
    # TF Serving's REST port (`test_tf_serving.py:107` hits :8500).
    parser.add_argument("--port", type=int, default=8500)
    parser.add_argument(
        "--model",
        action="append",
        default=[],
        metavar="NAME=CKPT_DIR",
        help="serve an orbax checkpoint as /v1/models/NAME (repeatable)",
    )
    parser.add_argument("--max-batch", type=int, default=64)
    parser.add_argument(
        "--batch-timeout-ms",
        type=float,
        default=None,
        metavar="MS",
        help="enable cross-request dynamic batching with this flush "
        "window (the TF-Serving batch_timeout_micros analog); "
        "concurrent requests merge into one accelerator execution",
    )
    parser.add_argument(
        "--apiserver",
        default=None,
        help="facade URL, or a comma-separated endpoint list for an "
        "active-passive HA pair (token via KFTPU_TOKEN, CA via "
        "KFTPU_CA); enables replica mode with --replica",
    )
    parser.add_argument(
        "--replica",
        default=None,
        metavar="NAME",
        help="ServingReplica object this worker embodies (replica mode)",
    )
    parser.add_argument("--namespace", default="default")
    parser.add_argument(
        "--advertise",
        default=None,
        metavar="HOST:PORT",
        help="endpoint to publish in ServingReplica status "
        "(default: 127.0.0.1:<port>)",
    )
    args = parser.parse_args()
    if bool(args.apiserver) != bool(args.replica):
        parser.error("--apiserver and --replica go together")

    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import resnet50, tiny_resnet
    from kubeflow_tpu.serving import (
        BatchingConfig,
        ModelRepository,
        ModelServerApp,
        Servable,
    )
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache
    from kubeflow_tpu.web.wsgi import serve

    # Every bucket program is compiled at load; a restarted or paged-in
    # server should find them compiled.
    enable_compile_cache()

    servables = []
    for spec in args.model:
        name, _, ckpt_dir = spec.partition("=")
        if not name or not ckpt_dir:
            parser.error(f"--model {spec!r} must be NAME=CKPT_DIR")
        servables.append(
            Servable.from_checkpoint(
                name,
                resnet50(),
                ckpt_dir,
                np.zeros((1, 224, 224, 3), np.float32),
                max_batch=args.max_batch,
                train=False,
            )
        )
    if not servables and not args.replica:
        module = tiny_resnet(num_classes=10)
        variables = jax.jit(module.init)(
            jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32)
        )
        servables.append(
            Servable.from_module(
                "demo", module, variables,
                max_batch=args.max_batch,
                warmup_example=np.zeros((32, 32, 3), np.float32),
                train=False,
            )
        )

    batching = (
        BatchingConfig(
            max_batch=args.max_batch, timeout_ms=args.batch_timeout_ms
        )
        if args.batch_timeout_ms is not None
        else None
    )
    repository = ModelRepository(servables)
    app = ModelServerApp(repository, batching=batching)
    server, thread = serve(app, host=args.host, port=args.port)
    logging.info(
        "model server on :%d serving %s",
        server.server_port, [s.name for s in servables],
    )

    if args.replica:
        from kubeflow_tpu.testing.apiserver_http import (
            HttpApiClient,
            endpoints_from_env,
        )

        client = HttpApiClient(endpoints_from_env(args.apiserver))
        endpoint = args.advertise or f"127.0.0.1:{server.server_port}"
        try:
            run_replica(
                client,
                args.replica,
                args.namespace,
                repository,
                build_servable=build_servable_from_rspec,
                endpoint=endpoint,
            )
        finally:
            app.close_batchers()
            client.close()
        return

    # Foreground serve: park on the server thread in bounded slices
    # (an untimed join would wedge silently if the server thread ever
    # stuck); ^C shuts the server down and bounds the final join.
    if threads.run_until_interrupt(thread):
        server.shutdown()
        app.close_batchers()
        threads.join_thread(
            thread, timeout=10.0, what="model server thread"
        )


if __name__ == "__main__":
    main()
