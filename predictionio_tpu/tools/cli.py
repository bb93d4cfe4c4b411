"""The `pio` command-line interface.

Rebuilds the reference's Console
(reference: tools/src/main/scala/io/prediction/tools/console/Console.scala:186-651):
same verbs, argparse instead of scopt, no spark-submit — train/eval/deploy
run in-process on the device mesh (Runner.scala's role collapses into a
plain function call; multi-host launch is env-driven via
parallel.mesh.init_distributed).

Verbs: version, status, build, train, eval, deploy, undeploy, eventserver,
dashboard, adminserver, app {new,list,show,delete,data-delete,channel-new,
channel-delete}, accesskey {new,list,delete}, template {list,get}, export,
import, trim, run; beyond-parity: update, servers, snapshot, faults,
rollback, spill {status,peek,requeue}.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import urllib.request
from typing import List, Optional

logger = logging.getLogger(__name__)


def _print(s=""):
    print(s)


# ---------------------------------------------------------------------------
# command implementations
# ---------------------------------------------------------------------------

def cmd_version(args) -> int:
    import predictionio_tpu
    _print(predictionio_tpu.__version__)
    return 0


def cmd_status(args) -> int:
    """(Console.scala:1033 status — verify storage + mesh).
    ``--telemetry`` (ISSUE 2) additionally polls the running servers'
    /stats.json + /traces.json and prints the compact operator view:
    counters, registry-derived latency percentiles, fold activity, and
    the slowest recent traces."""
    from predictionio_tpu.data.storage.registry import Storage
    _print("Inspecting storage backend connections...")
    results = Storage.verify_all_data_objects()
    for repo, ok in results.items():
        _print(f"  {repo}: {'OK' if ok else 'FAILED'} "
               f"({Storage.config_summary().get(repo, '?')})")
    _print("Inspecting device mesh...")
    if not _print_devices(args):
        return 1
    if getattr(args, "telemetry", False):
        _print_telemetry(args)
    if getattr(args, "slo", False):
        _print_slo(args)
    if all(results.values()):
        _print("Your system is all ready to go.")
        return 0
    return 1


_DEVICE_PROBE = (
    "import json; "
    "from predictionio_tpu.parallel.mesh import device_platform; "
    "print(json.dumps(device_platform()))")


def _print_devices(args) -> bool:
    """`pio status` owns no device (main() pinned it to the CPU), so it
    reports the chip without taking it: a live engine server says what
    it holds through /stats.json; with none listening, a short-lived
    child resolves the platform and exits, releasing the chip again."""
    import os
    import subprocess
    from predictionio_tpu.utils.http import fetch_json
    ip = getattr(args, "ip", None) or "127.0.0.1"
    port = getattr(args, "engine_port", 8000)
    st = fetch_json(f"http://{ip}:{port}/stats.json")
    if "platform" in st:
        _print(f"  held by the engine server at {ip}:{port} "
               f"(pid {st.get('pid')}): {st.get('deviceCount')} x "
               f"{st.get('deviceKind')} [{st['platform']}]")
        return True
    # `python -c` puts its cwd on sys.path: run it from the checkout
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        probe = subprocess.run(
            [sys.executable, "-c", _DEVICE_PROBE], cwd=repo,
            capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        _print("  device probe timed out after 120s")
        return False
    if probe.returncode != 0:
        tail = (probe.stderr.strip().splitlines() or ["no output"])[-1]
        _print(f"  device init failed: {tail}")
        return False
    dev = json.loads(probe.stdout.strip().splitlines()[-1])
    _print(f"  {dev['n']} x {dev['device_kind']} [{dev['platform']}] "
           f"(free: no engine server at {ip}:{port})")
    return True


def _status_targets(args):
    """(name, base_url) pairs `pio status --telemetry/--slo` poll:
    ``--url`` points the probes at ONE explicit fleet member (ISSUE 13
    satellite — any process on any host, not just the local default
    ports); the default stays the local engine + event server pair."""
    url = getattr(args, "url", None)
    if url:
        return [("member", url.rstrip("/"))]
    ip = getattr(args, "ip", None) or "127.0.0.1"
    return [
        ("engine", f"http://{ip}:{getattr(args, 'engine_port', 8000)}"),
        ("events", f"http://{ip}:"
                   f"{getattr(args, 'event_server_port', 7070)}"),
    ]


def _print_slo(args) -> None:
    """`pio status --slo` (ISSUE 6): each server's /health.json as a
    compact burn-rate table."""
    from predictionio_tpu.utils.http import fetch_json as _fetch_json
    targets = _status_targets(args)
    for name, base in targets:
        _print(f"{name.capitalize()} server SLOs...")
        h = _fetch_json(f"{base}/health.json")
        if "error" in h:
            _print(f"  unreachable: {h['error']}")
            continue
        _print(f"  overall: {h.get('status')}")
        for s in h.get("slo", ()):
            bits = [f"  {s.get('name', '?'):20s} {s.get('status'):8s}"]
            if s.get("burnFast") is not None:
                bits.append(f"burn fast/slow="
                            f"{s['burnFast']}/{s.get('burnSlow')}")
            if s.get("rateFast") is not None:
                bits.append(f"rate={s['rateFast']}/s "
                            f"(min {s.get('minRate')})")
            if s.get("value") is not None:
                bits.append(f"value={round(s['value'], 3)} "
                            f"(max {s.get('maxValue')})")
            if s.get("eventsFast") is not None:
                bits.append(f"events fast/slow={s['eventsFast']}/"
                            f"{s.get('eventsSlow')} "
                            f"(budget {s.get('budget')})")
            _print(" ".join(bits))


def _print_hist(name: str, h) -> None:
    if not isinstance(h, dict) or not h.get("count"):
        return
    _print(f"    {name}: n={h['count']} "
           f"p50={h.get('p50', 0) * 1000:.3f}ms "
           f"p95={h.get('p95', 0) * 1000:.3f}ms "
           f"p99={h.get('p99', 0) * 1000:.3f}ms")


def _print_telemetry(args) -> None:
    from predictionio_tpu.utils.http import fetch_json as _fetch_json
    targets = dict(_status_targets(args))
    engine = targets.get("engine") or targets.get("member")
    events = targets.get("events") or targets.get("member")

    _print("Engine server telemetry...")
    st = _fetch_json(f"{engine}/stats.json")
    if "error" in st:
        _print(f"  unreachable: {st['error']}")
    else:
        _print(f"  requests={st.get('requestCount')} "
               f"avgServing={st.get('avgServingSec', 0):.6f}s "
               f"avgPredict={st.get('avgPredictSec', 0):.6f}s")
        _print(f"  modelSwaps={st.get('modelSwaps')} "
               f"foldIns={st.get('foldIns')} "
               f"foldInEvents={st.get('foldInEvents')} "
               f"version={st.get('modelVersion')}")
        _print_hist("queryLatency", st.get("queryLatency"))
        _print_hist("batchWait", st.get("batchWait"))
        # compile plane (ISSUE 9): AOT registry + persistent-cache view
        aot = st.get("aot") or {}
        if aot:
            _print(f"  aot: resident={aot.get('executablesResident')} "
                   f"hitRate={aot.get('hitRate')} "
                   f"compiles={aot.get('compileCount')} "
                   f"({aot.get('compileSeconds')}s) "
                   f"sharedJits={len(aot.get('sharedJits', []))}")
            for label, bks in sorted(
                    (aot.get("bucketsCompiled") or {}).items()):
                _print(f"    {label}: {len(bks)} bucket(s) "
                       f"[{', '.join(bks[:4])}"
                       f"{', ...' if len(bks) > 4 else ''}]")
        xc = st.get("xlaCache") or {}
        if xc:
            _print(f"  xlaCache: entries={xc.get('entries')} "
                   f"hits={xc.get('hits')} misses={xc.get('misses')} "
                   f"dir={xc.get('dir')}")
        if st.get("swapToFirstQueryMs") is not None:
            _print(f"  swapToFirstQuery="
                   f"{st['swapToFirstQueryMs']:.1f}ms")
    _print("Event server telemetry...")
    ev = _fetch_json(f"{events}/stats.json?accessKey="
                     f"{getattr(args, 'accesskey', '') or ''}")
    if "error" in ev:
        _print(f"  unreachable or no --stats: {ev['error']}")
    else:
        cur = ev.get("currentWindow", {})
        _print(f"  window events={cur.get('count')} "
               f"byEvent={cur.get('byEvent')}")
    _print("Slowest recent traces (engine)...")
    traces = _fetch_json(
        f"{engine}/traces.json?n=5&sort=slowest").get("traces")
    if not traces:
        _print("  none")
    else:
        for t in traces:
            spans = t.get("root", {}).get("children", [])
            stages = ",".join(s.get("name", "?") for s in spans[:6])
            _print(f"  {t.get('kind'):14s} {t.get('durationMs', 0):>10}ms "
                   f"links={len(t.get('links', []))} [{stages}] "
                   f"{t.get('traceId')}")


def cmd_build(args) -> int:
    """Validate engine.json + factory import and register the engine
    manifest (the sbt-compile + RegisterEngine analog — Python engines need
    no compilation; Console.scala:924, RegisterEngine.scala)."""
    from predictionio_tpu.data.storage.base import EngineManifest
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.models import get_engine_factory
    with open(args.engine_json) as f:
        variant = json.load(f)
    factory_name = variant.get("engineFactory")
    if not factory_name:
        _print("engineFactory missing in engine.json")
        return 1
    factory = get_engine_factory(factory_name)
    engine = factory.apply()
    engine.json_to_engine_params(variant)
    manifest = EngineManifest(
        id=variant.get("id", "default"),
        version=str(variant.get("version", "0")),
        name=variant.get("id", factory_name),
        description=variant.get("description"),
        files=(args.engine_json,),
        engine_factory=factory_name)
    Storage.get_meta_data_engine_manifests().insert(manifest)
    _print(f"Engine {factory_name} is valid. Registered manifest "
           f"{manifest.id} {manifest.version}. Build finished successfully.")
    return 0


def cmd_unregister(args) -> int:
    """(Console unregister — remove the engine manifest)"""
    from predictionio_tpu.data.storage.registry import Storage
    with open(args.engine_json) as f:
        variant = json.load(f)
    mid = variant.get("id", "default")
    version = str(variant.get("version", "0"))
    if Storage.get_meta_data_engine_manifests().delete(mid, version):
        _print(f"Unregistered engine {mid} {version}.")
        return 0
    _print(f"Engine {mid} {version} is not registered.")
    return 1


def cmd_train(args) -> int:
    from predictionio_tpu.parallel.mesh import init_distributed
    from predictionio_tpu.workflow import (WorkflowConfig,
                                           create_workflow_main)
    init_distributed()  # no-op unless PIO_COORDINATOR/... are set
    config = WorkflowConfig(
        batch=args.batch or "",
        engine_variant=args.engine_json,
        engine_id=args.engine_id or "default",
        engine_version=args.engine_version or "0",
        engine_factory=args.engine_factory,
        engine_params_key=args.engine_params_key,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
        verbose=args.verbose)
    instance_id = create_workflow_main(config)
    _print(f"Training completed. Engine instance ID: {instance_id}")
    return 0


def cmd_eval(args) -> int:
    from predictionio_tpu.workflow import (WorkflowConfig,
                                           create_workflow_main)
    config = WorkflowConfig(
        batch=args.batch or "",
        engine_variant=args.engine_json,
        evaluation_class=args.evaluation_class,
        engine_params_generator_class=args.engine_params_generator_class)
    instance_id = create_workflow_main(config)
    _print(f"Evaluation completed. Evaluation instance ID: {instance_id}")
    return 0


def _serve_foreground(server, label: str) -> int:
    """Run a server in the foreground, stopping CLEANLY on SIGTERM/SIGINT
    (systemd/k8s stop, operator ^C): the listener stops accepting, the
    engine server's batcher fails any still-queued waiters loudly (no
    stranded request threads), and the mesh coordinator broadcasts the
    worker-release so executor processes exit instead of hanging in a
    collective. The handler fires server.stop() from a helper thread —
    calling shutdown from inside serve_forever's own thread deadlocks.
    (The reference's actor system gets this from its lifecycle; a bare
    HTTP loop has to do it explicitly.)"""
    import os
    import signal
    import threading
    import time

    torn_down = threading.Event()  # set when serve_forever returns

    def stopper():
        # stop() no-ops until the HTTP socket exists (a signal can land
        # during the up-to-3s bind-retry window, e.g. a systemd restart
        # racing the old instance), so retry until the serve loop is
        # actually torn down — observed via torn_down, NOT assumed: a
        # wedged drain or stuck collective must surface as a nonzero
        # exit to systemd/k8s, not masquerade as a clean stop
        deadline = time.time() + 15
        while time.time() < deadline:
            try:
                server.stop()
            except Exception:
                pass
            if torn_down.wait(0.5):
                return  # main thread's start() returned; exits 0 there
        if torn_down.is_set():
            return  # teardown landed exactly at the deadline — still clean
        _print(f"{label}: shutdown did not complete within 15s; "
               "hard-exiting with status 1.")
        os._exit(1)

    def on_sig(signum, frame):
        _print(f"{label}: received signal {signum}, shutting down.")
        threading.Thread(target=stopper, daemon=True).start()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, on_sig)
    server.start(background=False)
    torn_down.set()
    return 0


def _stop_stale_server(ip: str, port: int, wait_s: float = 30.0) -> None:
    """Undeploy a stale server occupying the target port, as the
    reference MasterActor does (CreateServer.scala:288-310), and WAIT
    for its listener to go away: the stale server is the process
    holding the chip, so the new one must not touch JAX before the old
    one is gone."""
    import http.client
    import socket
    import time
    try:
        req = urllib.request.Request(f"http://{ip}:{port}/stop",
                                     method="POST", data=b"")
        urllib.request.urlopen(req, timeout=3).read()
    except (OSError, http.client.HTTPException):
        return  # nothing listening (or not ours): nothing to stop
    _print(f"Undeployed a stale engine server on port {port}.")
    deadline = time.monotonic() + wait_s
    while time.monotonic() < deadline:
        try:
            socket.create_connection((ip, port), timeout=1).close()
        except OSError:
            return
        time.sleep(0.2)
    raise RuntimeError(
        f"stale engine server on {ip}:{port} acknowledged /stop but is "
        f"still listening after {wait_s:g}s; it still holds the chip")


def cmd_deploy(args) -> int:
    import os
    # primary only (mesh workers own no port, and probing from every
    # process could kill a peer's live server) — decided from the
    # launch environment, BEFORE the first JAX call
    if int(os.environ.get("PIO_PROCESS_ID", "0") or 0) == 0:
        _stop_stale_server(
            args.ip if args.ip != "0.0.0.0" else "127.0.0.1", args.port)
    from predictionio_tpu.parallel.mesh import init_distributed
    from predictionio_tpu.serving import EngineServer, ServerConfig
    init_distributed()  # no-op unless PIO_COORDINATOR/... are set
    config = ServerConfig(
        ip=args.ip, port=args.port,
        engine_instance_id=args.engine_instance_id,
        engine_id=args.engine_id or "default",
        engine_version=args.engine_version or "0",
        engine_variant=args.engine_json,
        feedback=args.feedback,
        event_server_ip=args.event_server_ip,
        event_server_port=args.event_server_port,
        accesskey=args.accesskey or "",
        mesh_broadcast_bytes=args.mesh_broadcast_bytes,
        canary_fraction=args.canary_fraction,
        canary_window_s=args.canary_window)
    server = EngineServer(config)
    server.load()
    if server.coordinator is not None and not server.coordinator.is_primary:
        # non-zero process of a multi-process mesh: no HTTP frontend —
        # mirror the primary's SPMD predict for every broadcast query
        # (the executor role; CreateServer.scala:490-641)
        _print("Mesh serve worker: mirroring the primary's query path.")
        server.serve_mesh_worker()
        return 0
    _print(f"Engine is deployed and running. Engine API is live at "
           f"http://{config.ip}:{config.port}.")
    return _serve_foreground(server, "engine server")


def cmd_update(args) -> int:
    """`pio update [--follow]` — attach the delta-training scheduler to a
    deployed engine (ISSUE 1 L6): tail the event store, fold fresh events
    into the served model, publish each folded version through the
    model-version registry, and POST /reload so the deployed server
    hot-swaps it. One-shot by default (a single forced tick); --follow
    loops until SIGINT."""
    import json as _json
    import time
    from predictionio_tpu.online import (DeltaTrainingScheduler,
                                         ModelVersionRegistry,
                                         SchedulerConfig)
    from predictionio_tpu.serving import EngineServer, ServerConfig

    # resolve engine + latest model exactly like deploy does, without
    # starting an HTTP frontend (EngineServer is the loader)
    from predictionio_tpu.parallel.mesh import DeviceUnavailable
    try:
        loader = EngineServer(ServerConfig(
            ip="127.0.0.1", port=0,
            engine_id=args.engine_id or "default",
            engine_version=args.engine_version or "0",
            engine_variant=args.engine_json,
            micro_batch=0))
    except DeviceUnavailable as e:
        # `pio update` folds on the device in ITS OWN process; on a
        # one-chip machine the deployed server already holds the chip
        from predictionio_tpu.utils.http import fetch_json
        _print(f"pio update cannot get a device: {e}")
        st = fetch_json(
            f"http://{args.engine_ip}:{args.engine_port}/stats.json")
        if st.get("platform") == "tpu":
            _print(f"The engine server at {args.engine_ip}:"
                   f"{args.engine_port} (pid {st.get('pid')}) holds "
                   f"{st.get('deviceCount')} x {st.get('deviceKind')}. "
                   "On a one-chip machine the fold runs inside the "
                   "serving process: admit the engine as a tenant of "
                   "tenancy.ServingHost with a scheduler "
                   "(docs/operations.md, \"Who owns the chip\").")
        return 1
    loader.load()
    _, ds_params = loader.engine_params.data_source_params
    app_name = args.app_name or getattr(ds_params, "app_name", None)
    if not app_name:
        _print("No app name: pass --app-name or set it in the variant's "
               "datasource params.")
        return 1
    config = SchedulerConfig(
        app_name=app_name,
        channel_name=getattr(ds_params, "channel_name", None),
        max_deltas=args.max_deltas,
        max_staleness_s=args.max_staleness,
        drift_ratio=args.drift_ratio,
        poll_interval_s=args.interval)
    reload_url = (f"http://{args.engine_ip}:{args.engine_port}/reload"
                  if args.engine_port else None)
    sched = DeltaTrainingScheduler(
        engine=loader.engine, engine_params=loader.engine_params,
        instance=loader.engine_instance, algorithms=loader.algorithms,
        models=loader.models, config=config,
        registry=ModelVersionRegistry(), reload_url=reload_url)
    if not args.follow:
        report = sched.tick(force=True)
        _print(_json.dumps(report or {"message": "no fresh events"}))
        return 0
    _print(f"Following app {app_name!r} (fold at {config.max_deltas} "
           f"deltas or {config.max_staleness_s:g}s staleness; ^C stops).")
    import logging as _logging
    # a following scheduler is a fleet member (ISSUE 13): its liveness
    # shows in `pio fleet status`, guards its flight series from GC,
    # and puts it on incident bundles' member roster
    from predictionio_tpu.obs import fleet as _fleet
    fleet_id = _fleet.register_member("scheduler")
    try:
        while True:
            try:
                report = sched.tick()
            except Exception:
                # transient tick failure (storage hiccup, solve error):
                # fold_in already restored its deltas for retry — the
                # follower must keep following, not die with a traceback
                _logging.getLogger(__name__).exception(
                    "update tick failed; retrying next interval")
                report = None
            if report:
                _print(_json.dumps(report))
            if sched.retrain_requested:
                _print("Drift bound exceeded — run `pio train` + "
                       "redeploy, then restart `pio update --follow`.")
                return 2
            time.sleep(config.poll_interval_s)
    except KeyboardInterrupt:
        _print("Stopped.")
        _print(_json.dumps(sched.stats()))
        return 0
    finally:
        _fleet.deregister_member(fleet_id)


def cmd_undeploy(args) -> int:
    """(Console undeploy — POST /stop to the deployed server)"""
    url = f"http://{args.ip}:{args.port}/stop"
    try:
        req = urllib.request.Request(url, method="POST", data=b"")
        with urllib.request.urlopen(req, timeout=10) as resp:
            resp.read()
        _print(f"Undeployed engine server at {args.ip}:{args.port}.")
        return 0
    except Exception as e:
        _print(f"Undeploy failed: {e}")
        return 1


def cmd_eventserver(args) -> int:
    from predictionio_tpu.data.api.event_server import (EventServer,
                                                        EventServerConfig)
    server = EventServer(EventServerConfig(ip=args.ip, port=args.port,
                                           stats=args.stats,
                                           max_batch=args.max_batch))
    _print(f"Event Server is listening on http://{args.ip}:{args.port}")
    return _serve_foreground(server, "event server")


def cmd_dashboard(args) -> int:
    from predictionio_tpu.tools.dashboard import Dashboard, DashboardConfig
    server = Dashboard(DashboardConfig(
        ip=args.ip, port=args.port,
        engine_url=args.engine_url,
        event_server_url=args.event_server_url))
    _print(f"Dashboard is listening on http://{args.ip}:{args.port}")
    return _serve_foreground(server, "dashboard")


def cmd_adminserver(args) -> int:
    from predictionio_tpu.tools.admin import AdminServer, AdminServerConfig
    server = AdminServer(AdminServerConfig(ip=args.ip, port=args.port))
    _print(f"Admin server is listening on http://{args.ip}:{args.port}")
    return _serve_foreground(server, "admin server")


def cmd_app(args) -> int:
    from predictionio_tpu.tools import app_commands as ac

    def show(desc):
        _print(f"    App Name: {desc.app.name}")
        _print(f"      App ID: {desc.app.id}")
        _print(f" Description: {desc.app.description or ''}")
        for k in desc.access_keys:
            events = ",".join(k.events) if k.events else "(all)"
            _print(f"  Access Key: {k.key} | {events}")
        for c in desc.channels:
            _print(f"     Channel: {c.name} (id {c.id})")

    try:
        if args.app_command == "new":
            desc = ac.app_new(args.name, app_id=args.id or 0,
                              description=args.description,
                              access_key=args.access_key or "")
            _print("Created a new app:")
            show(desc)
        elif args.app_command == "list":
            for desc in ac.app_list():
                keys = ", ".join(k.key for k in desc.access_keys)
                _print(f"{desc.app.id:4d} | {desc.app.name} | {keys}")
        elif args.app_command == "show":
            show(ac.app_show(args.name))
        elif args.app_command == "delete":
            if not args.force and not _confirm(
                    f"Delete app {args.name} and all its data?"):
                return 1
            ac.app_delete(args.name)
            _print(f"Deleted app {args.name}.")
        elif args.app_command == "data-delete":
            if not args.force and not _confirm(
                    f"Delete data of app {args.name}?"):
                return 1
            ac.app_data_delete(args.name, channel=args.channel,
                               delete_all=args.all)
            _print(f"Deleted data of app {args.name}.")
        elif args.app_command == "channel-new":
            c = ac.channel_new(args.name, args.channel)
            _print(f"Created channel {c.name} (id {c.id}) for app "
                   f"{args.name}.")
        elif args.app_command == "channel-delete":
            if not args.force and not _confirm(
                    f"Delete channel {args.channel} of app {args.name}?"):
                return 1
            ac.channel_delete(args.name, args.channel)
            _print(f"Deleted channel {args.channel}.")
        return 0
    except ac.AppCommandError as e:
        _print(str(e))
        return 1


def cmd_accesskey(args) -> int:
    from predictionio_tpu.tools import app_commands as ac
    try:
        if args.accesskey_command == "new":
            events = args.event or []
            k = ac.accesskey_new(args.app_name, key=args.key or "",
                                 events=events)
            _print(f"Created new access key: {k.key}")
        elif args.accesskey_command == "list":
            for k in ac.accesskey_list(args.app_name):
                events = ",".join(k.events) if k.events else "(all)"
                _print(f"{k.key} | app {k.appid} | {events}")
        elif args.accesskey_command == "delete":
            ac.accesskey_delete(args.key)
            _print(f"Deleted access key {args.key}.")
        return 0
    except ac.AppCommandError as e:
        _print(str(e))
        return 1


def cmd_template(args) -> int:
    """Template gallery: built-ins + an optional URI-addressed index
    (the reference's remote gallery mechanism, Template.scala:130-416;
    --gallery or PIO_TEMPLATE_GALLERY points at <root>/index.json)."""
    from predictionio_tpu.data.storage.registry import StorageError
    from predictionio_tpu.tools.templates import (GalleryError,
                                                  get_template,
                                                  list_templates)
    try:
        if args.template_command == "list":
            for name, desc in list_templates(gallery=args.gallery):
                _print(f"  {name:28s} {desc}")
            return 0
        return get_template(args.name, args.directory,
                            gallery=args.gallery)
    except (GalleryError, StorageError) as e:
        # StorageError: unregistered URI scheme from the adapter registry
        _print(f"Template gallery error: {e}")
        return 1


def cmd_export(args) -> int:
    from predictionio_tpu.tools.export_import import (
        export_events, export_events_parquet)
    if getattr(args, "format", "json") == "parquet":
        n = export_events_parquet(args.appid, args.output,
                                  channel_id=args.channelid)
    else:
        n = export_events(args.appid, args.output,
                          channel_id=args.channelid)
    _print(f"Exported {n} events to {args.output}.")
    return 0


def cmd_import(args) -> int:
    from predictionio_tpu.tools.export_import import (
        import_events, import_events_parquet, import_movielens)
    fmt = getattr(args, "format", "events")
    if fmt == "movielens":
        n = import_movielens(args.appid, args.input,
                             channel_id=args.channelid)
    elif fmt == "parquet":
        n = import_events_parquet(args.appid, args.input,
                                  channel_id=args.channelid)
    else:
        n = import_events(args.appid, args.input,
                          channel_id=args.channelid)
    _print(f"Imported {n} events.")
    return 0


def cmd_trim(args) -> int:
    """Copy a time window of events into a fresh app (the trim-app
    workflow: keep only a recent window under a new app id)."""
    from predictionio_tpu.data.event import parse_event_time
    from predictionio_tpu.tools.export_import import trim_events
    try:
        n = trim_events(
            args.src_appid, args.dst_appid,
            start_time=(parse_event_time(args.start)
                        if args.start else None),
            until_time=(parse_event_time(args.until)
                        if args.until else None),
            src_channel_id=args.src_channelid,
            dst_channel_id=args.dst_channelid)
    except ValueError as e:
        _print(f"Error: {e}")
        return 1
    _print(f"Trimmed {n} events from app {args.src_appid} into app "
           f"{args.dst_appid}.")
    return 0


def _engine_mesh_note(ip: str, port: int) -> str:
    """One-glance mesh-coordinator health for the `pio servers` engine
    row (round-4 verdict stretch: a poisoned coordinator — broadcast
    failed, every query 503s — was visible only to query traffic; the
    operator's redeploy signal should be explicit)."""
    try:
        with urllib.request.urlopen(
                f"http://{ip}:{port}/stats.json", timeout=3) as resp:
            mesh = json.loads(resp.read()).get("meshCoordinator")
    except Exception:
        return ""
    if not mesh:
        return ""
    if mesh.get("poisoned"):
        return "  MESH POISONED — redeploy"
    return f"  mesh {mesh.get('processes')}p healthy"


def cmd_servers(args) -> int:
    """Probe the stack's service ports and report what's live — the
    operator's one-glance view of the daemons pio-start-all manages
    (plus any deployed engine server)."""
    import urllib.error
    from concurrent.futures import ThreadPoolExecutor

    def probe(name, port):
        """(display row, is_up) — probes run concurrently so a dropped
        host costs one timeout, not four."""
        url = f"http://{args.ip}:{port}/"
        try:
            with urllib.request.urlopen(url, timeout=3) as resp:
                note = ""
                if name == "engine":
                    note = _engine_mesh_note(args.ip, port)
                return (f"  {name:14s} :{port:<6d} UP ({resp.status})"
                        f"{note}", True)
        except urllib.error.HTTPError as e:
            # an HTTP error still means something is listening
            return f"  {name:14s} :{port:<6d} UP ({e.code})", True
        except Exception:
            return f"  {name:14s} :{port:<6d} down", False

    targets = [("eventserver", args.event_server_port),
               ("engine", args.engine_port),
               ("dashboard", args.dashboard_port),
               ("adminserver", args.admin_port)]
    with ThreadPoolExecutor(len(targets)) as ex:
        rows = list(ex.map(lambda t: probe(*t), targets))
    for row, _ in rows:
        _print(row)
    return 0 if any(up for _, up in rows) else 1


def cmd_snapshot(args) -> int:
    """Durability verbs for the nativelog event store: shard files shipped
    to / restored from a URI-addressed blob store (data/storage/
    snapshot.py; the HBase snapshot-export role of the reference's
    replicated default store)."""
    from predictionio_tpu.data.storage import snapshot as S
    from predictionio_tpu.data.storage.registry import StorageError
    try:
        if args.snapshot_command == "create":
            m = S.create_snapshot(args.appid, args.uri, name=args.name,
                                  channel_id=args.channelid)
            total = sum(e["bytes"] for e in m["files"])
            _print(f"Snapshot {m['name']} created: {len(m['files'])} "
                   f"file(s), {total} bytes at {args.uri}.")
        elif args.snapshot_command == "restore":
            m = S.restore_snapshot(args.uri, args.name,
                                   app_id=args.appid,
                                   channel_id=args.channelid,
                                   force=args.force)
            _print(f"Snapshot {m['name']} restored "
                   f"({len(m['files'])} file(s)).")
        else:
            snaps = S.list_snapshots(args.uri)
            if not snaps:
                _print("No snapshots found.")
            for m in snaps:
                total = sum(e["bytes"] for e in m["files"])
                _print(f"  {m['name']}  app={m['app_id']} "
                       f"partitions={m['partitions']} files="
                       f"{len(m['files'])} bytes={total} "
                       f"created={m['created']}")
        return 0
    except (S.SnapshotError, StorageError) as e:
        # StorageError: e.g. an unregistered URI scheme from adapter_for
        _print(f"Snapshot failed: {e}")
        return 1


def cmd_bootstrap(args) -> int:
    """`pio bootstrap <tenant> --snapshot <name> --uri <root>` — stand up
    a new tenant from a snapshot through the bulk data plane (ISSUE 16):
    restore the shard files, train from the restored store via the
    streaming read, catch up the fold tail from the snapshot's creation
    instant, and (with --serve) admit the tenant onto a ServingHost only
    once caught up."""
    import json as _json
    from predictionio_tpu.dataplane import bootstrap_from_snapshot
    from predictionio_tpu.data.storage.registry import StorageError
    from predictionio_tpu.data.storage.snapshot import SnapshotError
    from predictionio_tpu.workflow.create_workflow import (WorkflowConfig,
                                                           _engine_and_params)

    variant, factory_name, engine, engine_params = _engine_and_params(
        WorkflowConfig(engine_variant=args.engine_json,
                       engine_factory=args.engine_factory))
    host = None
    if args.serve:
        from predictionio_tpu.tenancy import HostConfig, ServingHost
        host = ServingHost(HostConfig(ip=args.ip, port=args.port))
    try:
        report = bootstrap_from_snapshot(
            args.tenant, args.uri, args.snapshot,
            engine, engine_params,
            app_name=args.app_name, host=host,
            engine_id=variant.get("id") or None,
            engine_variant=args.engine_json,
            engine_factory=factory_name,
            force=args.force, stream=not args.no_stream,
            start_scheduler=args.serve)
    except (SnapshotError, StorageError, ValueError) as e:
        _print(f"Bootstrap failed: {e}")
        if host is not None:
            host.stop()
        return 1
    _print(_json.dumps(report.to_dict(), default=str))
    if host is None:
        return 0
    _print(f"Tenant {args.tenant!r} admitted; serving host live at "
           f"http://{args.ip}:{args.port}.")
    return _serve_foreground(host, "serving host")


def cmd_run(args) -> int:
    """(Console run — execute a main class/module in the pio environment)"""
    import runpy
    from predictionio_tpu.parallel.mesh import device_platform
    device_platform()
    sys.argv = [args.main_py] + (args.args or [])
    runpy.run_path(args.main_py, run_name="__main__")
    return 0


def cmd_faults(args) -> int:
    """Chaos-harness control (ISSUE 3): parse/validate a PIO_FAULTS
    spec, show what is active, and preview the seeded decision stream —
    the operator's dry run before pointing chaos at a live stack."""
    import os as _os

    from predictionio_tpu.resilience.faults import (ENV_VAR, FaultInjector,
                                                    FaultSpec, InjectedFault)
    spec_s = args.spec or _os.environ.get(ENV_VAR, "")
    if not spec_s.strip():
        _print(f"No fault spec: set {ENV_VAR} or pass --spec.")
        _print("Syntax: target:key=value[,key=value][;target:...]")
        _print("  e.g. 'storage.write:error=0.3,seed=42'")
        return 0
    try:
        spec = FaultSpec.parse(spec_s)
    except ValueError as e:
        _print(f"Invalid fault spec: {e}")
        return 1
    _print(f"Fault spec OK (seed={spec.seed if spec.seed is not None else 0}):")
    for target, rule in sorted(spec.rules.items()):
        bits = []
        if rule.error:
            bits.append(f"error={rule.error:g}")
        if rule.partition:
            bits.append(f"partition={rule.partition:g}")
        if rule.latency_ms:
            rate = 1.0 if rule.latency_rate is None else rule.latency_rate
            bits.append(f"latency={rule.latency_ms:g}ms@{rate:g}")
        if rule.corrupt:
            bits.append(f"corrupt={rule.corrupt:g}")
        _print(f"  {target:16s} {', '.join(bits) or '(no-op)'}")
    if args.preview:
        inj = FaultInjector(spec, sleep=lambda s: None)
        _print(f"First {args.preview} seeded decisions for "
               f"{args.target!r}:")
        for i in range(args.preview):
            try:
                inj.before(args.target)
                _print(f"  {i:3d}  ok")
            except InjectedFault:
                _print(f"  {i:3d}  ERROR (injected)")
            except ConnectionError:
                _print(f"  {i:3d}  PARTITION (injected)")
    active = _os.environ.get(ENV_VAR, "").strip()
    _print(f"{ENV_VAR} is "
           + (f"ACTIVE in this environment: {active}" if active
              else "not set (pass it to the server process to arm)"))
    return 0


def cmd_rollback(args) -> int:
    """`pio rollback` (ISSUE 5): demote every COMPLETED model version
    newer than the last-known-good pin (or an explicit --to instance)
    to ROLLEDBACK, so deploy//reload resolve the good version again,
    then POST /reload to the running engine server. The durable
    counterpart of the canary watchdog's in-memory rollback."""
    from predictionio_tpu.online import ModelVersionRegistry
    reg = ModelVersionRegistry()
    engine_id = args.engine_id or "default"
    engine_version = args.engine_version or "0"
    try:
        result = reg.rollback_to(engine_id, engine_version,
                                 args.engine_json, target_id=args.to)
    except ValueError as e:
        _print(f"Rollback failed: {e}")
        return 1
    _print(f"Rolled back to instance {result['target']}.")
    for iid in result["demoted"]:
        _print(f"  demoted {iid} -> ROLLEDBACK")
    if not args.engine_port:
        _print("No engine server to reload (--engine-port 0).")
        return 0
    url = f"http://{args.engine_ip}:{args.engine_port}/reload"
    try:
        req = urllib.request.Request(url, method="POST", data=b"")
        urllib.request.urlopen(req, timeout=30).read()
        _print(f"Reloaded engine server at {url}.")
    except Exception as e:
        _print(f"Reload failed ({e}); the server keeps its current "
               "model until it restarts or /reload succeeds.")
        return 1
    return 0


def cmd_incidents(args) -> int:
    """`pio incidents` (ISSUE 6): browse the postmortem bundles the
    diagnostics plane captured under <PIO_FS_BASEDIR>/incidents/ —
    list them, replay one as the lifecycle story it froze (flight
    records in order, trace links, provider states), or export a
    tar.gz for hand-off."""
    import json as _json

    from predictionio_tpu.obs.incidents import IncidentManager
    mgr = IncidentManager(incidents_dir=getattr(args, "dir", None))
    # --url (ISSUE 13 satellite): browse a FLEET MEMBER's bundles over
    # HTTP instead of the local base_dir — the operator box need not
    # share the member's filesystem
    url = (getattr(args, "url", None) or "").rstrip("/")
    sub = args.incidents_command
    if sub == "list":
        if url:
            from predictionio_tpu.utils.http import fetch_json
            body = fetch_json(f"{url}/incidents.json")
            if not isinstance(body, dict) or "incidents" not in body:
                _print(f"Cannot list incidents at {url}: "
                       f"{(body or {}).get('error') or (body or {}).get('message')}")
                return 1
            rows = body["incidents"]
            where = f"{url} ({body.get('incidentsDir')})"
        else:
            rows = mgr.list_incidents()
            where = mgr.incidents_dir()
        if not rows:
            _print(f"No incidents under {where}.")
            return 0
        for r in rows:
            ten = r.get("tenant")
            _print(f"{r['id']:40s} {r.get('kind', '?'):18s} "
                   f"{(ten or '-'):12s} "
                   f"{r.get('capturedAt', '')}  {r.get('reason', '')}")
        return 0
    if sub == "show":
        if url:
            from predictionio_tpu.utils.http import fetch_json
            bundle = fetch_json(f"{url}/incidents/{args.id}.json")
            if not isinstance(bundle, dict) or "id" not in bundle:
                _print(f"Cannot load incident {args.id} from {url}: "
                       f"{(bundle or {}).get('error') or (bundle or {}).get('message')}")
                return 1
        else:
            try:
                bundle = mgr.load(args.id)
            except (OSError, ValueError) as e:
                _print(f"Cannot load incident {args.id}: {e}")
                return 1
        _print(f"Incident {bundle['id']}: {bundle['kind']} — "
               f"{bundle['reason']}")
        _print(f"  captured: {bundle.get('capturedAt')}")
        if bundle.get("tenant"):
            _print(f"  tenant: {bundle['tenant']}")
        for name, state in (bundle.get("providers") or {}).items():
            _print(f"  [{name}] {_json.dumps(state, default=str)}")
        flight = bundle.get("flight") or []
        _print(f"  flight records ({len(flight)}, oldest first):")
        for rec in flight:
            extra = {k: v for k, v in rec.items()
                     if k not in ("seq", "t", "kind", "traceId",
                                  "modelVersion", "metrics")}
            _print(f"    #{rec.get('seq'):>6} {rec.get('kind', '?'):20s}"
                   f" trace={rec.get('traceId', '-'):16s}"
                   f" version={rec.get('modelVersion', '-')} "
                   f"{_json.dumps(extra, default=str) if extra else ''}")
        traces = bundle.get("traceDetail") or []
        if traces:
            _print(f"  traces ({len(traces)}):")
            for t in traces:
                _print(f"    {t.get('kind', '?'):14s} "
                       f"{t.get('traceId')} links={t.get('links')}")
        members = bundle.get("fleet") or []
        if members:
            _print(f"  fleet at capture ({len(members)} member(s)):")
            for m in members:
                _print(f"    {m.get('memberId', '?'):28s} "
                       f"{'ALIVE' if m.get('alive') else 'DEAD':6s}"
                       f" port={m.get('port') or '-'}"
                       + (f" [{m.get('error') or m.get('metricsError')}]"
                          if m.get("error") or m.get("metricsError")
                          else ""))
        return 0
    if sub == "export":
        if url:
            _print("export needs the member's filesystem; run it on "
                   "that host (list/show work over --url).")
            return 1
        try:
            out = mgr.export(args.id, getattr(args, "out", None))
        except (OSError, FileNotFoundError) as e:
            _print(f"Export failed: {e}")
            return 1
        _print(f"Exported incident {args.id} to {out}.")
        return 0
    _print("incidents subcommand must be list|show|export")
    return 1


def cmd_fleet(args) -> int:
    """`pio fleet {status,metrics,traces}` (ISSUE 13): the whole-fleet
    operator surface over the member registry under
    <PIO_FS_BASEDIR>/fleet/ — liveness, one federated {role,pid}-labeled
    metrics scrape, and a trace id stitched across every member's
    process into one waterfall."""
    from predictionio_tpu.obs import fleet as F
    reg = F.FleetRegistry(fleet_dir=getattr(args, "dir", None)) \
        if getattr(args, "dir", None) else F.get_fleet()
    sub = args.fleet_command
    if sub == "status":
        st = F.fleet_status(reg.members(), registry=reg)
        _print(f"Fleet under {st['fleetDir']} "
               f"(heartbeat {st['heartbeatS']:g}s, liveness window "
               f"{st['livenessWindowS']:g}s):")
        if not st["members"]:
            _print("  no members registered (are the servers running "
                   "with this PIO_FS_BASEDIR?)")
            return 1
        for m in st["members"]:
            url = m.get("url") or (F.member_url(m) or "-")
            _print(f"  {m.get('memberId', '?'):28s} "
                   f"{'UP' if m.get('alive') else 'DEAD':5s} "
                   f"pid={m.get('pid')} "
                   f"url={url:<28} "
                   f"beat {m.get('ageS', 0):.1f}s ago"
                   + (f" tenants={','.join(sorted(m['tenants']))}"
                      if m.get("tenants") else ""))
        _print(f"  {st['alive']} alive, {st['dead']} dead")
        return 0 if st["dead"] == 0 else 1
    if sub == "metrics":
        _print(F.federate_metrics(reg.live_members()).rstrip("\n"))
        return 0
    if sub == "traces":
        out = F.fleet_traces(args.id, members=reg.live_members(),
                             limit=args.n)
        for q in out["members"]:
            if not q.get("ok"):
                _print(f"# {q.get('memberId')}: {q.get('error')}")
        if not out["traces"]:
            _print(f"No member holds trace {args.id} (rings rotate; "
                   "capture an incident to freeze one).")
            return 1
        _print(f"Trace {args.id}: {len(out['traces'])} process-local "
               f"trace(s) across pids {out['pids']}")

        def walk(span, depth):
            _print(f"    {'  ' * depth}{span.get('name', '?'):24s} "
                   f"{span.get('durationMs', '?')}ms"
                   + (f" {span['attrs']}" if span.get("attrs") else ""))
            for c in span.get("children", ()):
                walk(c, depth + 1)

        for t in out["traces"]:
            m = t.get("member") or {}
            tag = " <- THE trace" if t.get("traceId") == args.id \
                else f" (links {t.get('links')})"
            _print(f"  [{m.get('role', '?')}:{t.get('pid', '?')}] "
                   f"{t.get('kind'):16s} {t.get('durationMs')}ms "
                   f"{t.get('traceId')}{tag}")
            walk(t.get("root") or {}, 1)
        return 0
    _print("fleet subcommand must be status|metrics|traces")
    return 1


def _default_spill_path() -> str:
    import os as _os
    from predictionio_tpu.data.storage.registry import base_dir
    return _os.path.join(base_dir(), "ingest_spill", "events.wal")


def cmd_spill(args) -> int:
    """`pio spill` (ISSUE 5 satellite): inspect the ingest spill WAL
    and its quarantine sidecar without reading raw files by hand —
    pending counts, peek at the oldest records, requeue quarantined
    ones after fixing their root cause."""
    import json as _json

    from predictionio_tpu.resilience.spill import (iter_pending,
                                                   read_quarantine,
                                                   requeue_quarantined,
                                                   scan_wal)
    path = args.wal or _default_spill_path()
    if args.spill_command == "status":
        s = scan_wal(path)
        if not s["exists"]:
            _print(f"No spill WAL at {path} (nothing ever spilled).")
            return 0
        _print(f"Spill WAL {path}:")
        _print(f"  records total/pending: {s['totalRecords']} / "
               f"{s['pendingRecords']}")
        _print(f"  bytes valid/pending:   {s['validBytes']} / "
               f"{s['pendingBytes']}")
        if s["tornBytes"]:
            _print(f"  torn tail: {s['tornBytes']} byte(s) (repaired on "
                   "the owning server's next open)")
        _print(f"  drain cursor: {s['cursor']}")
        _print(f"  quarantined:  {s['quarantined']} record(s)"
               + (f" in {path}.quarantine" if s["quarantined"] else ""))
        return 0
    if args.spill_command == "peek":
        shown = 0
        if args.quarantine:
            for rec in read_quarantine(path)[:args.n]:
                _print("QUARANTINED " + _json.dumps(rec, sort_keys=True))
                shown += 1
        else:
            for rec in iter_pending(path, limit=args.n):
                _print(_json.dumps(rec, sort_keys=True))
                shown += 1
        if shown == 0:
            _print("No pending spill records."
                   if not args.quarantine else "Quarantine is empty.")
        return 0
    if args.spill_command == "requeue":
        q = read_quarantine(path)
        if not q:
            _print("Quarantine is empty; nothing to requeue.")
            return 0
        if not args.force and not _confirm(
                f"Retry {len(q)} quarantined record(s) against the "
                "primary event store?"):
            return 1
        done, kept = requeue_quarantined(path)
        _print(f"Requeued {done} record(s) directly into the event "
               "store (id-deduped)."
               + (f" {kept} still-rejected record(s) remain "
                  f"quarantined in {path}.quarantine." if kept
                  else " Quarantine cleared."))
        return 0 if not kept else 1
    _print("spill command must be status|peek|requeue")
    return 1


def cmd_lint(args) -> int:
    """Static concurrency + JAX hot-path analyzer (ISSUE 8): the
    whole-repo AST pass behind the tier-1 zero-new-findings gate.
    Heavy lifting lives in analysis/runner.py; this shim forwards the
    already-parsed flags so `pio lint --json` and the standalone runner
    agree exactly."""
    from predictionio_tpu.analysis.runner import main as lint_main
    argv = []
    if args.json:
        argv.append("--json")
    if args.root:
        argv.extend(["--root", args.root])
    if args.baseline:
        argv.extend(["--baseline", args.baseline])
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.update_baseline:
        argv.append("--update-baseline")
    return lint_main(argv)


def cmd_cache(args) -> int:
    """`pio cache {status,clear}` (ISSUE 9): the persistent XLA compile
    cache — `$JAX_COMPILATION_CACHE_DIR` when set, else
    `<checkout>/.xla_cache`. `status` reports the directory, entry
    count/bytes and the process's hit/miss counters; `clear` removes
    the entries (safe live — jax re-creates them on the next
    compile)."""
    import json as _json
    from predictionio_tpu.compile.cache import (cache_status, clear_cache,
                                                enable_persistent_cache)
    if args.cache_cmd == "status":
        enable_persistent_cache()
        _print(_json.dumps(cache_status(), indent=2, default=str))
        return 0
    if args.cache_cmd == "clear":
        out = clear_cache()
        _print(_json.dumps(out))
        return 0
    _print("cache command must be status|clear")
    return 1


def cmd_tenants(args) -> int:
    """`pio tenants {list,status,signals,evict,pin,unpin}`: the
    multi-tenant serving host's operator surface — which engines are
    packed on the device, what each one's factor tables cost in HBM,
    the evict/pin levers the packing runbook uses, and the per-tenant
    SLO/cost signals row (ISSUE 17)."""
    import json as _json

    import urllib.error
    import urllib.request

    from predictionio_tpu.utils.http import fetch_json
    base = args.url.rstrip("/")
    sub = args.tenants_command

    def _post(path):
        try:
            req = urllib.request.Request(base + path, data=b"",
                                         method="POST")
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, _json.loads(resp.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, _json.loads(e.read())
            except Exception:
                return e.code, {"error": str(e)}
        except Exception as e:
            return None, {"error": str(e)}

    if sub in ("list", "status"):
        out = fetch_json(base + "/stats.json", timeout=10)
        if "error" in out:
            _print(f"serving host unreachable at {base}: "
                   f"{out['error']}")
            return 1
        tenants = out.get("tenants") or {}
        if getattr(args, "tenant", None):
            t = tenants.get(args.tenant)
            if t is None:
                _print(f"unknown tenant {args.tenant!r}; admitted: "
                       f"{sorted(tenants)}")
                return 1
            _print(_json.dumps(t, indent=2, default=str))
            return 0
        budget = out.get("budget") or {}
        bb = budget.get("budgetBytes")
        _print(f"Serving host at {base}: {len(tenants)} tenant(s), "
               f"{budget.get('residentBytes', 0)} HBM bytes resident"
               + (f" of {bb} budget" if bb else " (no budget)"))
        if sub == "list":
            for k in sorted(tenants):
                t = tenants[k]
                pin = " pinned" if t.get("pinned") else ""
                _print(f"  {k:20s} v={t.get('modelVersion') or '-':<18} "
                       f"hbm={t.get('hbmBytes', 0):>10} "
                       f"req={t.get('requests', 0):<8} "
                       f"evictions={t.get('evictions', 0)}{pin}")
            return 0
        _print(_json.dumps(tenants, indent=2, default=str))
        return 0
    if sub == "signals":
        out = fetch_json(base + "/tenants/signals.json", timeout=10)
        if "error" in out:
            _print(f"serving host unreachable at {base}: "
                   f"{out['error']}")
            return 1
        tenants = out.get("tenants") or {}
        if getattr(args, "tenant", None):
            t = tenants.get(args.tenant)
            if t is None:
                _print(f"unknown tenant {args.tenant!r}; admitted: "
                       f"{sorted(tenants)}")
                return 1
            _print(_json.dumps(t, indent=2, default=str))
            return 0
        _print(f"Serving host at {base}: {len(tenants)} tenant(s), "
               f"{out.get('residentBytes', 0)} HBM bytes resident")
        for k in sorted(tenants):
            t = tenants[k]
            p99 = t.get("serveP99Ms")
            _print(f"  {k:20s} {t.get('sloStatus', '?'):8s} "
                   f"rps={t.get('trafficEwmaRps', 0):<8} "
                   f"p99={'%.1fms' % p99 if p99 is not None else '-':<9} "
                   f"burn={t.get('burnFast')}/{t.get('burnSlow')} "
                   f"dev={t.get('deviceTimeShare', 0):<7} "
                   f"occ={t.get('occupancyShare', 0):<7} "
                   f"hbm={t.get('hbmBytes', 0):>10} "
                   f"stale={t.get('modelStalenessS', 0):.0f}s "
                   f"evictions={t.get('evictions', 0)}")
        return 0
    if sub in ("evict", "pin", "unpin"):
        st, out = _post(f"/tenants/{args.tenant}/{sub}")
        _print(_json.dumps(out, indent=2, default=str))
        return 0 if st == 200 else 1
    _print("tenants command must be list|status|evict|pin|unpin|signals")
    return 1


def cmd_placement(args) -> int:
    """`pio placement {status,plan,apply}` (ISSUE 18): the fleet
    tenant control plane's operator surface — where every tenant is
    placed (and under which generation), what the planner would do
    about budget pressure, and the lever that executes the planned
    migrations one observed step at a time."""
    import json as _json

    from predictionio_tpu.obs import fleet as F
    from predictionio_tpu.tenancy.controller import PlacementController
    reg = F.FleetRegistry(fleet_dir=getattr(args, "dir", None)) \
        if getattr(args, "dir", None) else F.get_fleet()
    ctl = PlacementController(registry=reg)
    sub = args.placement_command
    if sub == "status":
        st = ctl.status()
        if getattr(args, "json", False):
            _print(_json.dumps(st, indent=2, default=str))
            return 0
        hosts = st["hosts"]
        if not hosts:
            _print("no serving hosts registered (are they running "
                   "with this PIO_FS_BASEDIR?)")
            return 1
        for h in hosts:
            bb = h.get("budgetBytes")
            _print(f"{h['memberId']:28s} "
                   f"{'UP' if h['alive'] else 'DEAD':5s} "
                   f"{h.get('url') or '-':<26} "
                   f"hbm={h['usedBytes']}"
                   + (f"/{bb}" if bb else " (no budget)"))
            for k, t in h["tenants"].items():
                pin = " pinned" if t.get("pinned") else ""
                _print(f"    {k:20s} gen={t['generation']:<4} "
                       f"prio={t['priority']:<3} "
                       f"hbm={t['hbmBytes']:>10} "
                       f"rps={t['trafficEwmaRps']:<8} "
                       f"slo={t['sloStatus']}{pin}")
        slo = st.get("slo") or {}
        _print(f"controller SLO: {slo.get('status', 'no_data')}")
        dead_with_tenants = [h["memberId"] for h in hosts
                             if not h["alive"] and h["tenants"]]
        if dead_with_tenants:
            _print(f"DEAD hosts still holding tenants: "
                   f"{dead_with_tenants} (run a controller, or "
                   f"`pio placement apply` after it fails them over)")
            return 1
        return 0
    if sub == "plan":
        out = ctl.plan()
        decisions = out["rebalance"]["decisions"]
        if getattr(args, "json", False):
            _print(_json.dumps(out, indent=2, default=str))
            return 0
        if not decisions:
            _print("nothing to do: no live host is under budget "
                   "pressure")
            return 0
        for d in decisions:
            _print(f"  {d['action']:8s} {d['tenant']:20s} "
                   f"{d.get('fromHost', '-')} -> {d.get('host', '-')} "
                   f"({d.get('reason', '')})")
        return 0
    if sub == "apply":
        # one failover pass first (a dead host's stranded tenants are
        # more urgent than budget pressure), then the rebalance moves
        step = ctl.step()
        for a in step.get("actions", ()):
            _print(f"failover executed for {a['failover']}")
        moves = ctl.apply_rebalance()
        if not moves and not step.get("actions"):
            _print("nothing to do")
            return 0
        for m in moves:
            _print(f"migrated {m['tenant']}: {m['from']} -> {m['to']} "
                   f"(generation {m['generation']})")
        return 0
    _print("placement command must be status|plan|apply")
    return 1


def cmd_profile(args) -> int:
    """`pio profile top` (ISSUE 11): the running server's always-on
    sampling profiler, as a folded-stack top table — where the process
    spends its Python time RIGHT NOW, no restart, no instrumentation
    deploy. `pio profile trace {start,stop}` toggles the jax.profiler
    device trace on the same endpoint."""
    from predictionio_tpu.utils.http import fetch_json as _fetch_json
    base = f"http://{args.ip}:{args.port}"
    if args.profile_command == "top":
        out = _fetch_json(
            f"{base}/profile.json?action=report&top={args.n}")
        if "error" in out:
            _print(f"unreachable: {out['error']}")
            return 1
        _print(f"Sampling profiler at {base} "
               f"(hz={out.get('hz')}, samples={out.get('samples')}, "
               f"wall={out.get('wallS')}s, "
               f"overhead={out.get('overheadPct')}%)")
        stacks = out.get("topStacks") or []
        if not stacks:
            _print("  no samples yet (PIO_PROFILER=off, or the server "
                   "just started)")
            return 0
        for s in stacks:
            _print(f"  {s['pct']:6.2f}%  {s['count']:6d}  "
                   f"{s['stack']}")
        return 0
    if args.profile_command == "trace":
        import json as _json
        import urllib.request
        body = {"action": args.trace_action}
        if args.trace_action == "start" and args.dir:
            body["dir"] = args.dir
        req = urllib.request.Request(
            f"{base}/profile.json",
            data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                _print(_json.dumps(_json.loads(resp.read()), indent=2))
            return 0
        except Exception as e:
            _print(f"unreachable: {e}")
            return 1
    _print("profile command must be top|trace")
    return 1


def cmd_upgrade(args) -> int:
    """(Console upgrade / WorkflowUtils.checkUpgrade — the reference phones
    home for new versions; this build is offline, so upgrade is a no-op
    version report.)"""
    import predictionio_tpu
    _print(f"pio-tpu {predictionio_tpu.__version__}: offline build; "
           "no upgrade channel configured.")
    return 0


def _confirm(question: str) -> bool:
    answer = input(f"{question} (Y/n) ")
    return answer in ("", "y", "Y")


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio",
        description="pio-tpu: TPU-native machine-learning server")
    p.add_argument("--verbose", action="store_true")
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("version").set_defaults(func=cmd_version)
    st = sub.add_parser("status")
    st.add_argument("--telemetry", action="store_true",
                    help="also poll the running servers' /stats.json + "
                         "/traces.json and print the compact operator "
                         "view (counters, latency percentiles, fold "
                         "activity, slowest traces)")
    st.add_argument("--ip", default="127.0.0.1")
    st.add_argument("--engine-port", type=int, default=8000)
    st.add_argument("--event-server-port", type=int, default=7070)
    st.add_argument("--accesskey", default="",
                    help="event-server access key for its /stats.json")
    st.add_argument("--slo", action="store_true",
                    help="also poll the running servers' /health.json "
                         "and print each SLO's status and fast/slow "
                         "burn rates (ISSUE 6)")
    st.add_argument("--url",
                    help="point --telemetry/--slo at ONE explicit "
                         "fleet member (http://host:port) instead of "
                         "the local engine+event defaults (ISSUE 13)")
    st.set_defaults(func=cmd_status)

    b = sub.add_parser("build")
    _add_variant_arg(b)
    b.set_defaults(func=cmd_build)

    un = sub.add_parser("unregister")
    _add_variant_arg(un)
    un.set_defaults(func=cmd_unregister)

    t = sub.add_parser("train")
    _add_variant_arg(t)
    t.add_argument("--engine-id")
    t.add_argument("--engine-version")
    t.add_argument("--engine-factory")
    t.add_argument("--engine-params-key",
                   help="train with the factory's named programmatic "
                        "params instead of the variant JSON "
                        "(EngineFactory.engine_params(key))")
    t.add_argument("--batch")
    t.add_argument("--skip-sanity-check", action="store_true")
    t.add_argument("--stop-after-read", action="store_true")
    t.add_argument("--stop-after-prepare", action="store_true")
    t.set_defaults(func=cmd_train)

    e = sub.add_parser("eval")
    e.add_argument("evaluation_class")
    e.add_argument("engine_params_generator_class", nargs="?")
    _add_variant_arg(e)
    e.add_argument("--batch")
    e.set_defaults(func=cmd_eval)

    d = sub.add_parser("deploy")
    d.add_argument("--ip", default="0.0.0.0")
    d.add_argument("--port", type=int, default=8000)
    _add_variant_arg(d)
    d.add_argument("--engine-id")
    d.add_argument("--engine-version")
    d.add_argument("--engine-instance-id")
    d.add_argument("--feedback", action="store_true")
    d.add_argument("--event-server-ip", default="0.0.0.0")
    d.add_argument("--event-server-port", type=int, default=7070)
    d.add_argument("--accesskey")
    d.add_argument("--mesh-broadcast-bytes", type=int, default=1 << 16,
                   help="multi-process mesh query broadcast buffer size")
    d.add_argument("--canary-fraction", type=float, default=0.0,
                   help="guarded deploys (ISSUE 5): serve hot-swapped "
                        "model versions to this traffic fraction first "
                        "and auto-rollback on watchdog breach "
                        "(0 = swap immediately)")
    d.add_argument("--canary-window", type=float, default=30.0,
                   help="watchdog decision window seconds")
    d.set_defaults(func=cmd_deploy)

    u = sub.add_parser("undeploy")
    u.add_argument("--ip", default="127.0.0.1")
    u.add_argument("--port", type=int, default=8000)
    u.set_defaults(func=cmd_undeploy)

    upd = sub.add_parser(
        "update", help="online model updates: tail the event store, fold "
        "fresh events into the deployed model, publish versions, and "
        "/reload the serving process (ISSUE 1 delta-training)")
    _add_variant_arg(upd)
    upd.add_argument("--engine-id")
    upd.add_argument("--engine-version")
    upd.add_argument("--app-name",
                     help="event app (default: the variant's datasource "
                          "app_name)")
    upd.add_argument("--engine-ip", default="127.0.0.1",
                     help="deployed engine server to POST /reload to")
    upd.add_argument("--engine-port", type=int, default=8000,
                     help="deployed engine server port (0 = publish "
                          "only, no reload)")
    upd.add_argument("--follow", action="store_true",
                     help="keep tailing until ^C (default: one forced "
                          "fold-in tick)")
    upd.add_argument("--interval", type=float, default=2.0,
                     help="--follow poll cadence seconds")
    upd.add_argument("--max-deltas", type=int, default=256,
                     help="fold in after this many fresh events")
    upd.add_argument("--max-staleness", type=float, default=30.0,
                     help="... or once the oldest delta is this old (s)")
    upd.add_argument("--drift-ratio", type=float, default=1.5,
                     help="fold loss / anchor loss bound that escalates "
                          "to a full retrain")
    upd.set_defaults(func=cmd_update)

    ev = sub.add_parser("eventserver")
    ev.add_argument("--ip", default="0.0.0.0")
    ev.add_argument("--port", type=int, default=7070)
    ev.add_argument("--stats", action="store_true")
    ev.add_argument("--max-batch", type=int, default=50,
                    help="/batch/events.json size cap (default 50, the "
                         "reference wire limit); the columnar write "
                         "route has its own much larger bound")
    ev.set_defaults(func=cmd_eventserver)

    db = sub.add_parser("dashboard")
    db.add_argument("--ip", default="127.0.0.1")
    db.add_argument("--port", type=int, default=9000)
    db.add_argument("--engine-url", default="http://127.0.0.1:8000",
                    help="engine server the /telemetry view polls")
    db.add_argument("--event-server-url",
                    default="http://127.0.0.1:7070",
                    help="event server the /telemetry view polls")
    db.set_defaults(func=cmd_dashboard)

    adm = sub.add_parser("adminserver")
    adm.add_argument("--ip", default="127.0.0.1")
    adm.add_argument("--port", type=int, default=7071)
    adm.set_defaults(func=cmd_adminserver)

    a = sub.add_parser("app")
    asub = a.add_subparsers(dest="app_command", required=True)
    an = asub.add_parser("new")
    an.add_argument("name")
    an.add_argument("--id", type=int)
    an.add_argument("--description")
    an.add_argument("--access-key")
    asub.add_parser("list")
    ash = asub.add_parser("show")
    ash.add_argument("name")
    ad = asub.add_parser("delete")
    ad.add_argument("name")
    ad.add_argument("-f", "--force", action="store_true")
    add_ = asub.add_parser("data-delete")
    add_.add_argument("name")
    add_.add_argument("--channel")
    add_.add_argument("--all", action="store_true")
    add_.add_argument("-f", "--force", action="store_true")
    acn = asub.add_parser("channel-new")
    acn.add_argument("name")
    acn.add_argument("channel")
    acd = asub.add_parser("channel-delete")
    acd.add_argument("name")
    acd.add_argument("channel")
    acd.add_argument("-f", "--force", action="store_true")
    a.set_defaults(func=cmd_app)

    k = sub.add_parser("accesskey")
    ksub = k.add_subparsers(dest="accesskey_command", required=True)
    kn = ksub.add_parser("new")
    kn.add_argument("app_name")
    kn.add_argument("--key")
    kn.add_argument("--event", action="append")
    kl = ksub.add_parser("list")
    kl.add_argument("app_name", nargs="?")
    kd = ksub.add_parser("delete")
    kd.add_argument("key")
    k.set_defaults(func=cmd_accesskey)

    tp = sub.add_parser("template")
    tsub = tp.add_subparsers(dest="template_command", required=True)
    tl = tsub.add_parser("list")
    tl.add_argument("--gallery", help="template index URI "
                    "(default: $PIO_TEMPLATE_GALLERY)")
    tg = tsub.add_parser("get")
    tg.add_argument("name")
    tg.add_argument("directory")
    tg.add_argument("--gallery", help="template index URI "
                    "(default: $PIO_TEMPLATE_GALLERY)")
    tp.set_defaults(func=cmd_template)

    ex = sub.add_parser("export")
    ex.add_argument("--appid", type=int, required=True)
    ex.add_argument("--output", required=True)
    ex.add_argument("--channelid", type=int)
    ex.add_argument("--format", choices=["json", "parquet"],
                    default="json",
                    help="json = one wire-format event per line; "
                         "parquet = columnar (the reference's default "
                         "format, EventsToFile.scala:35)")
    ex.set_defaults(func=cmd_export)

    im = sub.add_parser("import")
    im.add_argument("--appid", type=int, required=True)
    im.add_argument("--input", required=True)
    im.add_argument("--channelid", type=int)
    im.add_argument("--format",
                    choices=["events", "parquet", "movielens"],
                    default="events",
                    help="events = JSON-lines (pio export's output); "
                         "parquet = pio export --format parquet output; "
                         "movielens = a real ML-100K u.data / "
                         "ML-20M ratings.csv file, directory, or .zip")
    im.set_defaults(func=cmd_import)

    tr = sub.add_parser("trim")
    tr.add_argument("--src-appid", type=int, required=True)
    tr.add_argument("--dst-appid", type=int, required=True)
    tr.add_argument("--start", help="ISO8601; keep events at/after this")
    tr.add_argument("--until", help="ISO8601; keep events before this")
    tr.add_argument("--src-channelid", type=int)
    tr.add_argument("--dst-channelid", type=int)
    tr.set_defaults(func=cmd_trim)

    sv = sub.add_parser("servers",
                        help="probe the stack's service ports")
    sv.add_argument("--ip", default="127.0.0.1")
    sv.add_argument("--event-server-port", type=int, default=7070)
    sv.add_argument("--engine-port", type=int, default=8000)
    sv.add_argument("--dashboard-port", type=int, default=9000)
    sv.add_argument("--admin-port", type=int, default=7071)
    sv.set_defaults(func=cmd_servers)

    sn = sub.add_parser(
        "snapshot", help="ship/restore nativelog shard snapshots to a "
        "remote blob URI (the HBase snapshot/export role)")
    snsub = sn.add_subparsers(dest="snapshot_command", required=True)
    sc = snsub.add_parser("create")
    sc.add_argument("--appid", type=int, required=True)
    sc.add_argument("--uri", required=True,
                    help="remote blob root, e.g. file:///backups")
    sc.add_argument("--name", help="snapshot name (default: UTC stamp)")
    sc.add_argument("--channelid", type=int)
    sr = snsub.add_parser("restore")
    sr.add_argument("--uri", required=True)
    sr.add_argument("--name", required=True)
    sr.add_argument("--appid", type=int,
                    help="restore into a different app id")
    sr.add_argument("--channelid", type=int)
    sr.add_argument("--force", action="store_true",
                    help="replace an existing non-empty namespace")
    sl = snsub.add_parser("list")
    sl.add_argument("--uri", required=True)
    sn.set_defaults(func=cmd_snapshot)

    bs = sub.add_parser(
        "bootstrap", help="stand up a new tenant from a snapshot: "
        "restore, train through the streaming bulk data plane, catch "
        "up the fold tail, then admit (ISSUE 16)")
    bs.add_argument("tenant", help="tenant key for the new slot")
    bs.add_argument("--snapshot", required=True, help="snapshot name")
    bs.add_argument("--uri", required=True,
                    help="snapshot blob root, e.g. file:///backups")
    _add_variant_arg(bs)
    bs.add_argument("--engine-factory")
    bs.add_argument("--app-name",
                    help="app to restore + train into (default: the "
                         "variant's datasource app_name)")
    bs.add_argument("--force", action="store_true",
                    help="replace an existing non-empty namespace")
    bs.add_argument("--no-stream", action="store_true",
                    help="train through the monolithic batch read "
                         "instead of the streaming data plane")
    bs.add_argument("--serve", action="store_true",
                    help="start a ServingHost and admit the tenant "
                         "once caught up (default: report only)")
    bs.add_argument("--ip", default="0.0.0.0")
    bs.add_argument("--port", type=int, default=8100)
    bs.set_defaults(func=cmd_bootstrap)

    r = sub.add_parser("run")
    r.add_argument("main_py")
    r.add_argument("args", nargs="*")
    r.set_defaults(func=cmd_run)

    up = sub.add_parser("upgrade")
    up.set_defaults(func=cmd_upgrade)

    ln = sub.add_parser(
        "lint", help="static concurrency + JAX hot-path analyzer "
        "(ISSUE 8): lock-order cycles, locks held across blocking "
        "calls, unguarded background-thread mutation, implicit host "
        "syncs, jit recompile hazards, hot-path cost. Exit 0 = zero "
        "findings outside conf/lint_baseline.json")
    ln.add_argument("--json", action="store_true",
                    help="machine-readable report (CI mode)")
    ln.add_argument("--root", default=None,
                    help="directory to analyze (default: the "
                         "predictionio_tpu package)")
    ln.add_argument("--baseline", default=None,
                    help="baseline file (default: conf/lint_baseline"
                         ".json)")
    ln.add_argument("--no-baseline", action="store_true",
                    help="report every finding, suppressing nothing")
    ln.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline to the current finding "
                         "set (new entries get TODO justifications "
                         "you must edit)")
    ln.set_defaults(func=cmd_lint)

    ca = sub.add_parser(
        "cache", help="persistent XLA compile cache (ISSUE 9): the "
        "executable store at $JAX_COMPILATION_CACHE_DIR (default "
        "<checkout>/.xla_cache) that makes warmup compiles a "
        "once-per-machine cost")
    casub = ca.add_subparsers(dest="cache_cmd", required=True)
    casub.add_parser("status")
    casub.add_parser("clear")
    ca.set_defaults(func=cmd_cache)

    tn = sub.add_parser(
        "tenants", help="multi-tenant serving host (ISSUE 15): list "
        "the engines packed on one device, read their per-tenant HBM "
        "cost, and evict/pin tenants under the budget manager")
    tnsub = tn.add_subparsers(dest="tenants_command", required=True)
    tnl = tnsub.add_parser("list")
    tns = tnsub.add_parser("status")
    tns.add_argument("tenant", nargs="?",
                     help="one tenant's full status (default: all)")
    tne = tnsub.add_parser("evict")
    tne.add_argument("tenant")
    tnp = tnsub.add_parser("pin")
    tnp.add_argument("tenant")
    tnu = tnsub.add_parser("unpin")
    tnu.add_argument("tenant")
    tng = tnsub.add_parser(
        "signals", help="per-tenant SLO/cost signals: traffic, serve "
        "p50/p99, burn rates, HBM bytes, device-time and occupancy "
        "shares, staleness, evictions (ISSUE 17)")
    tng.add_argument("tenant", nargs="?",
                     help="one tenant's signals row (default: all)")
    for tsp in (tnl, tns, tne, tnp, tnu, tng):
        tsp.add_argument("--url", default="http://localhost:8100",
                         help="serving host base URL")
    tn.set_defaults(func=cmd_tenants)

    rb = sub.add_parser(
        "rollback", help="guarded deploys (ISSUE 5): demote model "
        "versions newer than the last-known-good pin and /reload the "
        "serving process")
    _add_variant_arg(rb)
    rb.add_argument("--engine-id")
    rb.add_argument("--engine-version")
    rb.add_argument("--to", metavar="INSTANCE_ID",
                    help="explicit rollback target (default: the "
                         "last-good pin, else the previous COMPLETED "
                         "version)")
    rb.add_argument("--engine-ip", default="127.0.0.1")
    rb.add_argument("--engine-port", type=int, default=8000,
                    help="deployed engine server to POST /reload to "
                         "(0 = registry-only, no reload)")
    rb.set_defaults(func=cmd_rollback)

    spl = sub.add_parser(
        "spill", help="inspect the durable ingest-spill WAL and its "
        "quarantine sidecar (ISSUE 3 spill, ISSUE 5 tooling)")
    spsub = spl.add_subparsers(dest="spill_command", required=True)
    sps = spsub.add_parser("status")
    sps.add_argument("--wal", help="WAL path (default: "
                     "<PIO_FS_BASEDIR>/ingest_spill/events.wal)")
    spp = spsub.add_parser("peek")
    spp.add_argument("n", type=int, nargs="?", default=10,
                     help="records to show (default 10)")
    spp.add_argument("--wal")
    spp.add_argument("--quarantine", action="store_true",
                     help="peek the quarantine sidecar instead of the "
                          "pending WAL records")
    spr = spsub.add_parser("requeue")
    spr.add_argument("--wal")
    spr.add_argument("-f", "--force", action="store_true")
    spl.set_defaults(func=cmd_spill)

    inc = sub.add_parser(
        "incidents", help="browse the diagnostics plane's postmortem "
        "bundles (ISSUE 6): automatic captures from rollbacks, "
        "sentinel breaches, gate rejections and breaker opens")
    incsub = inc.add_subparsers(dest="incidents_command", required=True)
    inl = incsub.add_parser("list")
    inl.add_argument("--dir", help="incidents dir (default: "
                     "<PIO_FS_BASEDIR>/incidents)")
    inl.add_argument("--url", help="browse a fleet member's bundles "
                     "over HTTP (http://host:port) instead of the "
                     "local base_dir (ISSUE 13)")
    ins = incsub.add_parser("show")
    ins.add_argument("id")
    ins.add_argument("--dir")
    ins.add_argument("--url", help="load the bundle from a fleet "
                     "member over HTTP instead of the local base_dir")
    ine = incsub.add_parser("export")
    ine.add_argument("id")
    ine.add_argument("--out", help="output path (default ./<id>.tar.gz)")
    ine.add_argument("--dir")
    ine.add_argument("--url", help="rejected with a pointer (export "
                     "needs the member's filesystem)")
    inc.set_defaults(func=cmd_incidents)

    fl = sub.add_parser(
        "fleet", help="fleet observability (ISSUE 13): member registry "
        "liveness, the federated {role,pid}-labeled metrics scrape, "
        "and cross-process trace stitching")
    flsub = fl.add_subparsers(dest="fleet_command", required=True)
    fls = flsub.add_parser("status")
    fls.add_argument("--dir", help="fleet registry dir (default: "
                     "<PIO_FS_BASEDIR>/fleet)")
    flm = flsub.add_parser("metrics")
    flm.add_argument("--dir")
    flt = flsub.add_parser("traces")
    flt.add_argument("id", help="the trace id to stitch fleet-wide "
                     "(e.g. the traceId an event POST returned)")
    flt.add_argument("-n", type=int, default=50,
                     help="per-member neighborhood cap")
    flt.add_argument("--dir")
    fl.set_defaults(func=cmd_fleet)

    pf = sub.add_parser(
        "profile", help="runtime attribution (ISSUE 11): read the "
        "running server's always-on sampling profiler, or toggle a "
        "jax.profiler device trace")
    pfsub = pf.add_subparsers(dest="profile_command", required=True)
    pft = pfsub.add_parser("top")
    pft.add_argument("-n", type=int, default=20,
                     help="stacks to show (default 20)")
    pft.add_argument("--ip", default="127.0.0.1")
    pft.add_argument("--port", type=int, default=8000,
                     help="server to read (engine 8000; the event "
                          "server exposes the same endpoint behind "
                          "--stats)")
    pftr = pfsub.add_parser("trace")
    pftr.add_argument("trace_action", choices=("start", "stop"))
    pftr.add_argument("--dir", help="trace output dir (start only)")
    pftr.add_argument("--ip", default="127.0.0.1")
    pftr.add_argument("--port", type=int, default=8000)
    pf.set_defaults(func=cmd_profile)

    pl = sub.add_parser(
        "placement", help="fleet tenant control plane (ISSUE 18): "
        "per-host placements and generations, the rebalance plan, and "
        "one-shot failover + migration execution")
    plsub = pl.add_subparsers(dest="placement_command", required=True)
    pls = plsub.add_parser("status")
    pls.add_argument("--dir", help="fleet registry dir (default: "
                     "<PIO_FS_BASEDIR>/fleet)")
    pls.add_argument("--json", action="store_true",
                     help="full machine-readable status")
    plp = plsub.add_parser("plan")
    plp.add_argument("--dir")
    plp.add_argument("--json", action="store_true")
    pla = plsub.add_parser("apply")
    pla.add_argument("--dir")
    pl.set_defaults(func=cmd_placement)

    fl = sub.add_parser(
        "faults", help="chaos-harness control: validate a PIO_FAULTS "
        "spec and preview its seeded decisions")
    fl.add_argument("--spec", help="fault spec (default: $PIO_FAULTS)")
    fl.add_argument("--preview", type=int, default=0, metavar="N",
                    help="print the first N seeded decisions")
    fl.add_argument("--target", default="storage.write",
                    help="target for --preview (default storage.write)")
    fl.set_defaults(func=cmd_faults)

    return p


def _add_variant_arg(sp):
    """The engine-variant file flag shared by build/unregister/train/
    eval/deploy; --variant/-v are the reference's spellings
    (Console.scala:161)."""
    sp.add_argument("--engine-json", "--variant", "-v",
                    dest="engine_json", default="engine.json",
                    help="engine variant JSON (reference: --variant/-v)")


#: verbs whose process computes on the device; each resolves the
#: platform through parallel.mesh.device_platform. Every other verb is
#: pinned to the CPU before it can touch JAX.
DEVICE_VERBS = frozenset(
    {"train", "eval", "deploy", "update", "run", "bootstrap"})


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="[%(levelname)s] [%(name)s] %(message)s")
    args = build_parser().parse_args(argv)
    if args.command not in DEVICE_VERBS:
        from predictionio_tpu.parallel.mesh import host_only
        host_only()
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
