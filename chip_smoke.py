#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, each
its own OS process, at the full width of the recommendation template
(explicit ALS, rank 200, the ML-20M vocabulary of 138,493 users x 26,744
items; ratings generated from a seed in ML-20M's shape):

    store populate -> pio eventserver (REST singles + /events/columnar.json)
    -> pio train -> pio deploy -> POST /queries.json -> pio status /
    pio update against the live server -> pio undeploy

and checks numbers, not just liveness, with numpy only (see `check_rows`
and `check_served`). It exits non-zero, printing no result line, when any
child fails, any assertion fails, or JAX finds no TPU. On success the LAST
line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as the train and serve processes themselves reported it.

This process never touches the chip: it pins itself to the CPU backend
before anything else, orchestrates children and checks their results with
numpy. The children get the environment unchanged, so they resolve the
platform exactly as a user's processes would (`parallel.mesh.
device_platform`: a TPU, or an error — the CPU only under an explicit
JAX_PLATFORMS=cpu).

    python3 chip_smoke.py            # the chip run: full width, 20M events
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny
                                     # same control flow at toy size on the
                                     # CPU; only the platform assertion is
                                     # relaxed (it prints platform=cpu)

Phase wall times are printed as observations of THIS smoke on the named
device. They are not a benchmark and carry no metric name of one.
"""

import jax  # noqa: E402  (first: pin before anything can touch a device)

jax.config.update("jax_platforms", "cpu")

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))
PIO = os.path.join(REPO, "bin", "pio")
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
APP = "ChipSmoke"

#: (n_users, n_items, events, rank) — the chip run and the --tiny control-
#: flow run. --tiny keeps every class the full run has: items above 75% of
#: their row bucket (so the background bucket promotion fires), items with
#: K >= rank, rank > K >= 32 and K < 32.
FULL = (138_493, 26_744, 20_000_000, 200)
TINY = (1_500, 200, 40_000, 48)
ITERATIONS, LAM, SEED = 2, 0.01, 3

#: relative L2 tolerance of a persisted item row against its float64
#: re-solve, by the compute dtype the trainer reports. bfloat16: the Gram
#: inputs are rounded to 8 significant bits (unit roundoff u = 2^-8 =
#: 3.9e-3) before an f32-accumulated einsum and an f32 CG solve; the
#: rounding errors are independent across the K*R products, so they
#: average in the Gram and the solve amplifies what is left by the
#: (regularized, modest) condition number. Measured on a v5e at this
#: width: worst row 3.0e-3 (K in [32, 200)), heaviest rows 2.0e-4.
#: 2e-2 = 5 u leaves room for another seed while a wrong solve (a row of
#: another entity, a dropped regularizer on a short row, a non-converged
#: CG) is off by O(1). float32 (the CPU path: LAPACK cholesky) measures
#: 3e-5.
ROW_TOL = {"bfloat16": 2e-2, "float32": 1e-3}


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

class Phases:
    """Wall time per phase, in order; a phase that raises ends the run."""

    def __init__(self):
        self.rows = []

    def run(self, name, fn, *args, **kw):
        print(f"[chip_smoke] >>> {name}", flush=True)
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.add(name, time.perf_counter() - t0)
        return out

    def add(self, name, seconds, source="chip_smoke wall"):
        self.rows.append((name, float(seconds), source))
        print(f"[chip_smoke] <<< {name}: {seconds:.2f}s ({source})",
              flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)
    print(f"[chip_smoke] ok: {msg}", flush=True)


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method, url, body=None, timeout=60):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        ctype = resp.headers.get("Content-Type", "")
    return json.loads(raw) if "json" in ctype else raw.decode()


def wait_http(url, proc, log_path, timeout_s):
    deadline = time.monotonic() + timeout_s
    while True:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{url}: process exited with {proc.returncode} before "
                f"listening; tail of {log_path}:\n{tail(log_path)}")
        try:
            urllib.request.urlopen(url, timeout=2).read()
            return
        except (urllib.error.URLError, ConnectionError, socket.timeout):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{url} not listening after {timeout_s}s; tail of "
                    f"{log_path}:\n{tail(log_path)}")
            time.sleep(0.25)


def tail(path, n=40):
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def run_pio(args, env, log_name, timeout):
    """One `bin/pio` verb to completion; output kept under OUT_DIR."""
    log_path = os.path.join(OUT_DIR, log_name)
    with open(log_path, "w") as log:
        proc = subprocess.run([sys.executable, PIO] + args, env=env,
                              stdout=log, stderr=subprocess.STDOUT,
                              timeout=timeout)
    return proc.returncode, log_path


def start_pio(args, env, log_name):
    log_path = os.path.join(OUT_DIR, log_name)
    log = open(log_path, "w")
    proc = subprocess.Popen([sys.executable, PIO] + args, env=env,
                            stdout=log, stderr=subprocess.STDOUT)
    log.close()   # the child holds its own descriptor
    return proc, log_path


def maps_libtpu(pid):
    """Has the process loaded libtpu (i.e. created a TPU client)?"""
    with open(f"/proc/{pid}/maps") as f:
        return "libtpu" in f.read()


def device_memory(metrics_text):
    """{device: {kind: bytes}} from pio_jax_device_memory_bytes."""
    out = {}
    for m in re.finditer(
            r'pio_jax_device_memory_bytes\{([^}]*)\}\s+([0-9.eE+-]+)',
            metrics_text):
        labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
        out.setdefault(labels["device"], {})[labels["kind"]] = \
            int(float(m.group(2)))
    return out


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def synthetic_ratings(n_users, n_items, nnz, seed):
    """Power-law item popularity + lognormal user activity, ML-20M's
    shape. Returns event-ordered (user, item, rating) with repeats: a
    later event for the same pair overrides the earlier one (the
    template's latest-wins dedup)."""
    rng = np.random.default_rng(seed)
    raw = rng.lognormal(mean=0.0, sigma=1.1, size=n_users)
    counts = np.maximum(1, (raw / raw.sum() * nnz)).astype(np.int64)
    counts[0] += max(nnz - counts.sum(), 1 - counts[0])
    user = np.repeat(np.arange(n_users, dtype=np.int32), counts)
    pop = 1.0 / np.arange(1, n_items + 1) ** 1.1
    item = rng.choice(n_items, size=user.shape[0],
                      p=pop / pop.sum()).astype(np.int32)
    rating = rng.integers(1, 6, size=user.shape[0]).astype(np.float32)
    # event order is not user order: shuffle so the REST tail and every
    # store chunk hold a mix of users
    order = rng.permutation(user.shape[0])
    return user[order], item[order], rating[order]


def event_times(lo, hi):
    """ISO-8601 event times, one millisecond apart from the epoch."""
    t = (1000 + np.arange(lo, hi)).astype("datetime64[ms]")
    return np.char.add(np.datetime_as_string(t, unit="ms"), "Z").tolist()


def storage_env(base):
    return {
        "PIO_FS_BASEDIR": os.path.join(base, "store"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NATIVELOG",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITE_URL": os.path.join(base, "pio.db"),
        "PIO_STORAGE_SOURCES_NATIVELOG_TYPE": "nativelog",
        "PIO_STORAGE_SOURCES_NATIVELOG_PATH": os.path.join(base, "evlog"),
        "PIO_STORAGE_SOURCES_NATIVELOG_PARTITIONS": "8",
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_HOSTS": os.path.join(base, "models"),
    }


def populate(user, item, rating, n_bulk):
    """The bulk of the events, as set-up, through the store's columnar
    write (the route an operator's bulk import takes) — in THIS process,
    while the event server idles."""
    from predictionio_tpu.data.columnar import ColumnarBatch
    from predictionio_tpu.data.storage.registry import Storage
    app = Storage.get_meta_data_apps().get_by_name(APP)
    events = Storage.get_events()
    events.init(app.id)
    users = np.array([f"u{k}" for k in range(int(user.max()) + 1)],
                     dtype=object)
    items = np.array([f"i{k}" for k in range(int(item.max()) + 1)],
                     dtype=object)
    props = np.array([{"rating": float(r)} for r in range(6)],
                     dtype=object)
    chunk = 500_000
    for lo in range(0, n_bulk, chunk):
        hi = min(lo + chunk, n_bulk)
        events.insert_columnar(ColumnarBatch(
            hi - lo, "rate", "user", users[user[lo:hi]].tolist(),
            target_entity_type="item",
            target_entity_id=items[item[lo:hi]].tolist(),
            properties=props[rating[lo:hi].astype(np.int64)].tolist(),
            event_time=event_times(lo, hi)), app.id)
    events.close()
    return app.id


def rest_events(base_url, key, user, item, rating, lo, n_single):
    """The tail of the event sequence through the event server's REST
    routes: `n_single` POST /events.json, the rest as ONE POST
    /events/columnar.json."""
    hi = len(user)
    times = event_times(lo, hi)
    for j in range(n_single):
        i = lo + j
        out = http("POST", f"{base_url}/events.json?accessKey={key}", {
            "event": "rate", "entityType": "user",
            "entityId": f"u{user[i]}", "targetEntityType": "item",
            "targetEntityId": f"i{item[i]}",
            "properties": {"rating": float(rating[i])},
            "eventTime": times[j]})
        assert "eventId" in out, f"POST /events.json #{j}: {out}"
    check(True, f"{n_single} POST /events.json acknowledged")
    sl = slice(lo + n_single, hi)
    out = http("POST", f"{base_url}/events/columnar.json?accessKey={key}", {
        "event": "rate", "entityType": "user",
        "entityId": [f"u{u}" for u in user[sl]],
        "targetEntityType": "item",
        "targetEntityId": [f"i{i}" for i in item[sl]],
        "properties": [{"rating": float(r)} for r in rating[sl]],
        "eventTime": times[n_single:]})
    check(out.get("eventsCreated") == hi - lo - n_single,
          f"POST /events/columnar.json created {hi - lo - n_single} events")


# ---------------------------------------------------------------------------
# numeric checks (numpy only)
# ---------------------------------------------------------------------------

def load_model(instance_id):
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.parallel.sharded_table import is_sharded
    blob = Storage.get_model_data_models().get(instance_id)
    check(blob is not None, f"model blob of instance {instance_id} stored")
    model = pickle.loads(blob.models)[0]   # written by this run's trainer

    def table(t):
        return np.asarray(t.to_numpy() if is_sharded(t) else t)
    return (table(model.als.user_factors), table(model.als.item_factors),
            model.user_ix, model.item_ix)


def dedup_latest(user, item, rating, n_items):
    """Keep the LAST event of each (user, item) pair."""
    pair = user.astype(np.int64) * n_items + item
    _, last_rev = np.unique(pair[::-1], return_index=True)
    keep = np.sort(len(pair) - 1 - last_rev)
    return user[keep], item[keep], rating[keep]


def check_rows(U, V, user_dense, item_dense, ratings, rank, lam, tol):
    """(a) The item sweep is the last half-sweep of training, so every
    persisted item row must equal (U_S^T U_S + lam*n*I)^-1 U_S^T r over
    its n raters S, from the persisted USER table. Re-solve ~64 sampled
    rows in float64: the heaviest, and some of each solver route (padded
    segment K >= rank: primal CG kernel; rank > K >= 32: dual system
    through the kernel; K < 32: dual system through jnp CG)."""
    from predictionio_tpu.ops.ratings import bucket_lengths
    u, i, r = ratings
    order = np.argsort(i, kind="stable")
    counts = np.bincount(i, minlength=int(i.max()) + 1)
    starts = np.concatenate([[0], np.cumsum(counts)])
    ladder = bucket_lengths(int(counts.max()))
    K = ladder[np.searchsorted(ladder, np.maximum(counts, 1))]
    rng = np.random.default_rng(0)

    def some(mask, n):
        ids = np.nonzero(mask & (counts > 0))[0]
        return rng.choice(ids, size=min(n, ids.size), replace=False)

    classes = {
        "heaviest": np.argsort(-counts)[:8],
        "K>=rank": some(K >= rank, 16),
        "rank>K>=32": some((K < rank) & (K >= 32), 24),
        "K<32": some(K < 32, 16),
    }
    U64 = U.astype(np.float64)
    worst = {}
    for name, ids in classes.items():
        check(len(ids) > 0, f"row check has items in class {name}")
        errs = []
        for it in ids:
            sl = order[starts[it]:starts[it + 1]]
            Us = U64[user_dense[u[sl]]]
            A = Us.T @ Us + lam * len(sl) * np.eye(rank)
            ref = np.linalg.solve(A, Us.T @ r[sl].astype(np.float64))
            got = V[item_dense[it]].astype(np.float64)
            errs.append(np.linalg.norm(got - ref)
                        / max(np.linalg.norm(ref), 1e-30))
        worst[name] = float(max(errs))
        check(worst[name] <= tol,
              f"{len(ids)} item rows [{name}] match the float64 re-solve: "
              f"worst relative error {worst[name]:.2e} <= {tol:.0e}")
    return worst


def check_served(U, V, user_ix, item_ix, answers):
    """(b) Every served list is a correct top-k of U[u] . V^T over the
    persisted tables. The device scores in f32 with the TPU's default
    matmul precision (operands rounded to bfloat16, u = 2^-8, f32
    accumulation) and the readback packs scores to float16 (2^-11), so a
    served score s of item i may differ from the exact dot by at most
    e_i = (2u + u^2) * sum_r |U_ur V_ir| + 2^-11 |s|. Required: each
    served score is within e_i of the exact score of the served id, the
    list is sorted, and no unserved item beats a served one by more
    than the two error bounds (a near-tie may legitimately swap)."""
    u_round = 2.0 ** -8
    V64 = V.astype(np.float64)
    absV = np.abs(V64)
    exact_lists = 0
    for (user, num), served in answers:
        q = U[user_ix.get(user)].astype(np.float64)
        exact = V64 @ q
        bound = (2 * u_round + u_round ** 2) * (absV @ np.abs(q)) \
            + 2.0 ** -11 * np.abs(exact) + 1e-6
        assert len(served) == min(num, len(exact)), (
            f"user {user} num={num}: {len(served)} items served")
        ids = np.array([item_ix.get(s["item"]) for s in served])
        got = np.array([s["score"] for s in served])
        assert (ids >= 0).all(), f"user {user}: unknown item id served"
        assert len(set(ids.tolist())) == len(ids), f"user {user}: repeats"
        assert np.all(np.abs(got - exact[ids]) <= bound[ids]), (
            f"user {user}: served scores {got} vs exact {exact[ids]} "
            f"exceed the bf16/f16 bound {bound[ids]}")
        assert np.all(np.diff(got) <= 0), f"user {user}: not sorted"
        top = np.argsort(-exact, kind="stable")[:len(ids)]
        kth = exact[top[-1]]
        slack = bound[ids] + bound[top[-1]]
        assert np.all(exact[ids] >= kth - slack), (
            f"user {user}: served {ids} is not a top-{num} within the "
            f"error bound (exact top: {top})")
        exact_lists += int(np.array_equal(ids, top))
    check(True, f"{len(answers)} served lists are correct top-k within "
                f"the bf16-matmul + f16-pack bound ({exact_lists} equal "
                f"numpy's float64 ranking id for id)")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy size, for JAX_PLATFORMS=cpu: same control "
                         "flow, platform assertion relaxed")
    ap.add_argument("--events", type=int,
                    help="events to generate (default: 20,000,000; "
                         "--tiny 40,000)")
    ap.add_argument("--factor-sharding", default="replicated",
                    choices=("replicated", "model"),
                    help="ALSAlgorithmParams.factor_sharding (the "
                         "second four-chip variant)")
    args = ap.parse_args()
    if not os.path.exists(PIO):
        raise SystemExit("chip_smoke.py: bin/pio not found beside this "
                         "script — run it from a checkout of the repo")
    args.n_users, args.n_items, n_events, args.rank = \
        TINY if args.tiny else FULL
    args.events = args.events or n_events
    phases = Phases()

    # which device does a user's process get? Asked of a process of its
    # own, before anything is written: no accelerator, no run
    def probe():
        out = subprocess.run(
            [sys.executable, "-c",
             "import json; from predictionio_tpu.parallel.mesh import "
             "device_platform; print(json.dumps(device_platform()))"],
            env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
            text=True, timeout=300)
        if out.returncode != 0:
            raise SystemExit("chip_smoke.py: no accelerator for a pio "
                             "process:\n" + out.stderr[-2000:])
        return json.loads(out.stdout.strip().splitlines()[-1])
    probed = phases.run("device probe (own process)", probe)
    print(f"[chip_smoke] platform={probed['platform']} "
          f"device_kind={probed['device_kind']} n={probed['n']}", flush=True)
    if probed["platform"] != "tpu" and not args.tiny:
        raise SystemExit(
            f"chip_smoke.py: no accelerator (platform="
            f"{probed['platform']}); the chip run needs a TPU. "
            f"`JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny` checks the "
            f"control flow on the CPU.")

    # start from what git would commit: the native store library is
    # rebuilt from native/eventlog.cpp (its staleness test is by mtime,
    # and mtimes do not survive a copy), bytecode from source
    shutil.rmtree(os.path.join(REPO, "native", "build"), ignore_errors=True)
    for root, dirs, _ in os.walk(REPO):
        if "__pycache__" in dirs:
            shutil.rmtree(os.path.join(root, "__pycache__"))
            dirs.remove("__pycache__")
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    sys.path.insert(0, REPO)

    work = tempfile.mkdtemp(prefix="pio_chip_smoke_")
    os.environ.update(storage_env(work))
    child_env = dict(os.environ)
    # the suite's hermetic switches must not reach the children: this
    # run is ABOUT the compile cache and the deploy-time warm
    for k in ("PIO_XLA_CACHE", "PIO_AOT_WARM", "PIO_AOT", "PIO_SERVE_PACK"):
        child_env.pop(k, None)
    cache_dir = (child_env.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(REPO, ".xla_cache"))
    procs = []
    try:
        result = run(args, phases, procs, work, child_env, cache_dir)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        shutil.rmtree(work, ignore_errors=True)

    device = result["device"]
    label = f"{device['count']} x {device['kind']} [{device['platform']}]"
    print(f"\n[chip_smoke] phase walls — smoke observations on {label}, "
          f"not a benchmark:")
    for name, seconds, source in phases.rows:
        print(f"  {name:28s} {seconds:9.2f} s   ({source})")
    result["phases"] = [{"phase": n, "seconds": round(s, 3), "source": src}
                        for n, s, src in phases.rows]
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def run(args, phases, procs, work, env, cache_dir):
    n_users, n_items, rank = args.n_users, args.n_items, args.rank
    # -- data ---------------------------------------------------------------
    user, item, rating = phases.run(
        "generate events", synthetic_ratings, n_users, n_items,
        args.events, SEED)
    n_events = len(user)
    n_rest, n_single = min(72, n_events // 2), 8
    n_bulk = n_events - n_rest

    rc, log = run_pio(["app", "new", APP], env, "app_new.log", 120)
    check(rc == 0, "pio app new exited 0")
    key = re.search(r"Access Key: (\S+)", open(log).read()).group(1)

    # -- event server: up for the rest of the run ---------------------------
    # The REST events carry the LATEST event times of the sequence and go
    # in first, into an empty store: the first columnar write of an event
    # server costs O(events already in the store) (74 s into 5M events on
    # the chip host), which is the store's business, not this smoke's.
    es_port = free_port()
    es_url = f"http://127.0.0.1:{es_port}"
    es, es_log = start_pio(["eventserver", "--ip", "127.0.0.1", "--port",
                            str(es_port), "--stats"], env, "eventserver.log")
    procs.append(es)
    phases.run("event server start", wait_http, es_url + "/", es, es_log, 120)
    phases.run("REST events", rest_events, es_url, key, user, item, rating,
               n_bulk, n_single)
    phases.run("populate store (set-up)", populate, user, item, rating,
               n_bulk)

    # -- train ---------------------------------------------------------------
    engine_json = os.path.join(work, "engine.json")
    with open(engine_json, "w") as f:
        json.dump({
            "id": "default", "description": "chip_smoke",
            "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": APP}},
            "preparator": {"params": {"dedup": "latest"}},
            "algorithms": [{"name": "als", "params": {
                "rank": rank, "num_iterations": ITERATIONS, "lam": LAM,
                "seed": SEED,
                "factor_sharding": args.factor_sharding}}]}, f)
    def listing(d):
        return set(os.listdir(d)) if os.path.isdir(d) else set()
    cache_before = listing(cache_dir)
    default_before = listing(os.path.join(REPO, ".xla_cache"))
    rc, train_log = phases.run(
        "pio train (whole process)", run_pio,
        ["train", "--engine-json", engine_json], env, "train.log", 1500)
    check(rc == 0, f"pio train exited 0 (log: {train_log})")
    text = open(train_log).read()
    report = json.loads(
        re.search(r"Train report: (\{.*\})", text).group(1))
    instance_id = re.search(r"Engine instance ID: (\S+)", text).group(1)
    algo = report["algorithm"]
    for name, k in (("read", "read"), ("prepare", "prepare")):
        phases.add(f"  train: {name}", report["stages"][k], "train report")
    for name, k in (("plan", "plan_s"), ("upload", "upload_s"),
                    ("compile", "compile_s"), ("sweeps", "sweeps_s"),
                    ("fetch", "fetch_s")):
        phases.add(f"  train: {name}", algo[k], "train report")
    print(f"[chip_smoke] train process reports platform="
          f"{report['platform']} device_kind={report['device_kind']} "
          f"n={report['device_count']} solver={algo['solver']} "
          f"compute_dtype={algo['compute_dtype']} "
          f"sweep_chunk={algo['sweep_chunk']} "
          f"mesh_devices={algo['n_devices']}", flush=True)
    if not args.tiny:
        check(report["platform"] == "tpu",
              "the train process ran on platform=tpu")
    n_dev = report["device_count"]
    want = {"cpu": "cholesky"}.get(
        report["platform"], "cg_pallas" if algo["n_devices"] == 1 else "cg")
    check(algo["solver"] == want,
          f"solver that ran is {want} ({report['platform']}, "
          f"{algo['n_devices']} mesh device(s))")
    check(algo["n_devices"] == n_dev,
          f"the resolved mesh spans all {n_dev} device(s)")
    for d in report.get("devices", []):
        print(f"[chip_smoke] after train {d['device']}: bytes_in_use="
              f"{d['bytes_in_use']} peak_bytes_in_use="
              f"{d['peak_bytes_in_use']}", flush=True)
        check(d["peak_bytes_in_use"] > 0,
              f"{d['device']} held data during train")
    if report["platform"] == "tpu":
        check(len(report.get("devices", [])) == n_dev,
              f"train reported memory of all {n_dev} device(s)")
    cache_train = listing(cache_dir)
    check(len(cache_train) > 0,
          f"after pio train {cache_dir} holds {len(cache_train)} "
          f"compile-cache entries ({len(cache_train - cache_before)} "
          f"written by this train; the rest it found there)")

    from predictionio_tpu.data.storage.registry import Storage
    inst = Storage.get_meta_data_engine_instances().get(instance_id)
    check(inst.status == "COMPLETED"
          and inst.env.get("platform") == report["platform"]
          and inst.env.get("device_kind") == report["device_kind"]
          and inst.env.get("device_count") == str(n_dev)
          and inst.env.get("solver") == algo["solver"],
          f"EngineInstance {instance_id} env carries "
          f"{ {k: inst.env.get(k) for k in ('platform', 'device_kind', 'device_count', 'solver', 'compute_dtype')} }")

    # -- (a) persisted rows vs float64 --------------------------------------
    U, V, user_ix, item_ix = phases.run("load persisted model", load_model,
                                        instance_id)
    ratings = dedup_latest(user, item, rating, n_items)
    n_u, n_i = len(np.unique(ratings[0])), len(np.unique(ratings[1]))
    print(f"[chip_smoke] events={n_events} distinct ratings trained="
          f"{len(ratings[0])} rank={rank} vocabulary={U.shape[0]} x "
          f"{V.shape[0]}", flush=True)
    check(U.shape == (n_u, rank) and V.shape == (n_i, rank),
          f"persisted tables are {n_u} x {rank} and {n_i} x {rank}")
    if not args.tiny and n_events == FULL[2]:
        check((n_u, n_i) == FULL[:2], "the full ML-20M vocabulary trained")
    check(bool(np.isfinite(U).all() and np.isfinite(V).all()),
          "persisted tables are finite")
    user_dense = user_ix.to_indices_array(
        np.array([f"u{k}" for k in range(n_users)]))
    item_dense = item_ix.to_indices_array(
        np.array([f"i{k}" for k in range(n_items)]))
    row_err = phases.run(
        "check rows vs float64", check_rows, U, V, user_dense, item_dense,
        ratings, rank, LAM, ROW_TOL[algo["compute_dtype"]])

    # -- deploy --------------------------------------------------------------
    port = free_port()
    url = f"http://127.0.0.1:{port}"

    def deploy(label, log_name):
        t0 = time.perf_counter()
        proc, log_path = start_pio(
            ["deploy", "--engine-json", engine_json, "--ip", "127.0.0.1",
             "--port", str(port)], env, log_name)
        procs.append(proc)
        wait_http(url + "/", proc, log_path, 900)
        phases.add(f"{label} to listening", time.perf_counter() - t0)
        st = http("GET", url + "/stats.json")
        phases.add(f"  {label}: AOT warm", st["aotWarm"]["wallS"],
                   "/stats.json")
        return proc, st

    def undeploy(label, proc):
        rc, _ = phases.run(
            label, run_pio,
            ["undeploy", "--ip", "127.0.0.1", "--port", str(port)], env,
            "undeploy.log", 120)
        check(rc == 0 and proc.wait(timeout=60) == 0,
              f"{label} stopped the engine server (exit 0)")

    srv, warm = deploy("pio deploy", "deploy.log")
    print(f"[chip_smoke] serve process reports platform={warm['platform']} "
          f"deviceKind={warm['deviceKind']} deviceCount="
          f"{warm['deviceCount']} solver={warm['solver']} computeDtype="
          f"{warm['computeDtype']} pid={warm['pid']}", flush=True)
    check((warm["platform"], warm["deviceKind"], warm["deviceCount"])
          == (report["platform"], report["device_kind"], n_dev),
          "the serve process reports the same device as the trainer")
    check((warm["solver"], warm["computeDtype"])
          == (algo["solver"], algo["compute_dtype"]),
          "/stats.json carries the solver and compute dtype of the "
          "loaded model's training")
    check(warm["aotWarm"]["compiled"] > 0 and warm["aotWarm"]["failed"] == 0
          and warm["aot"]["failedBuckets"] == 0,
          f"deploy-time warm compiled {warm['aotWarm']['compiled']} "
          f"bucket(s), none failed")
    pcache = warm["aot"]["pcache"]
    check(warm["xlaCache"]["dir"] == cache_dir,
          f"the deploy process caches in {cache_dir} "
          f"(persistent-cache hits={pcache['hits']} "
          f"misses={pcache['misses']} at warm)")
    mem_warm = device_memory(http("GET", url + "/metrics"))
    for dev, kinds in sorted(mem_warm.items()):
        print(f"[chip_smoke] after deploy-warm {dev}: bytes_in_use="
              f"{kinds.get('bytes_in_use')} peak_bytes_in_use="
              f"{kinds.get('peak_bytes_in_use')}", flush=True)

    # -- queries -------------------------------------------------------------
    def ask(user_id, num):
        return http("POST", url + "/queries.json",
                    {"user": user_id, "num": num})
    heavy_users = np.argsort(-np.bincount(ratings[0]))[:4]
    rng = np.random.default_rng(1)
    known = [f"u{k}" for k in np.concatenate(
        [heavy_users, rng.choice(n_users, size=44, replace=False)])]
    t0 = time.perf_counter()
    first = ask(known[0], 10)
    phases.add("first query", time.perf_counter() - t0)
    answers = [((known[0], 10), first["itemScores"])]
    nums = (1, 5, 10, 16)
    todo = [(u, nums[j % 4]) for j, u in enumerate(known[1:])]
    scraped = []

    def scrape_event_server():
        scraped.append(http("GET", es_url + "/metrics"))
    gate = threading.Barrier(17)

    def fire(q):
        if q is None:
            gate.wait()
            return scrape_event_server()
        return q, ask(*q)["itemScores"]

    def worker(chunk):
        gate.wait()
        return [fire(q) for q in chunk]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(17) as pool:
        futures = [pool.submit(worker, todo[w::16]) for w in range(16)]
        futures.append(pool.submit(fire, None))
        for f in futures[:16]:
            answers.extend(f.result())
        futures[16].result()
    phases.add(f"{len(todo)} queries, 16 concurrent",
               time.perf_counter() - t0)
    check("pio_event" in scraped[0],
          "event server /metrics scraped while the engine server served")
    if report["platform"] == "tpu":
        check(not maps_libtpu(es.pid) and maps_libtpu(srv.pid),
              f"the event server (pid {es.pid}) created no TPU client; "
              f"the engine server (pid {srv.pid}) holds it")
    else:
        check(not maps_libtpu(es.pid),
              f"the event server (pid {es.pid}) created no TPU client")
    check(http("GET", es_url + "/") is not None,
          "the event server still answers")
    unknown = ask("nobody-knows-this-user", 5)
    check(unknown["itemScores"] == [], "an unknown user gets an empty list")
    hits0 = http("GET", url + "/stats.json")["resultCache"]["hits"]
    repeat = ask(known[0], 10)
    stats = http("GET", url + "/stats.json")
    check(repeat == first and stats["resultCache"]["hits"] == hits0 + 1,
          "a repeated query is answered by the result cache")
    phases.run("check served top-k", check_served, U, V, user_ix, item_ix,
               answers)

    # -- /stats.json after the queries --------------------------------------
    aot = stats["aot"]
    check(stats["maxBatchSize"] > 1,
          f"concurrent queries formed batches > 1 (max "
          f"{stats['maxBatchSize']}, avg {stats['avgBatchSize']:.2f} over "
          f"{stats['batches']})")
    check(aot["failedBuckets"] == 0, "aot.failedBuckets == 0")
    check(aot["dispatchFallbacks"] == {}, "aot.dispatchFallbacks is empty")
    check(aot["dispatchMisses"] == {} and aot["hitRate"] == 1.0,
          f"aot.hitRate == 1.0 on served buckets "
          f"({sum(aot['dispatchHits'].values())} dispatches, no miss)")
    new = {b for bs in aot["bucketsCompiled"].values() for b in bs} - \
        {b for bs in warm["aot"]["bucketsCompiled"].values() for b in bs}
    i_warm = {int(re.search(r"(?:^|-)i(\d+)", b).group(1))
              for bs in warm["aot"]["bucketsCompiled"].values() for b in bs}
    check(all(int(re.search(r"(?:^|-)i(\d+)", b).group(1)) > max(i_warm)
              for b in new),
          f"0 compilations on the request path after the warm; "
          f"{len(new)} background promotion(s) of the item bucket "
          f"{sorted(new)}")
    print(f"[chip_smoke] deploy process persistent cache: hits="
          f"{aot['pcache']['hits']} misses={aot['pcache']['misses']} "
          f"dir={stats['xlaCache']['dir']} entries="
          f"{stats['xlaCache']['entries']}", flush=True)
    mem_q = device_memory(http("GET", url + "/metrics"))
    for dev, kinds in sorted(mem_q.items()):
        print(f"[chip_smoke] after queries {dev}: bytes_in_use="
              f"{kinds.get('bytes_in_use')} peak_bytes_in_use="
              f"{kinds.get('peak_bytes_in_use')}", flush=True)
    if report["platform"] == "tpu":
        check(len(mem_q) == n_dev and any(
            k.get("bytes_in_use", 0) > 0 for k in mem_q.values()),
            f"the engine server reports memory of all {n_dev} device(s)")

    # -- the chip has one owner ----------------------------------------------
    rc, status_log = phases.run(
        "pio status beside the server", run_pio,
        ["status", "--engine-port", str(port)], env, "status.log", 300)
    check(rc == 0 and "held by the engine server" in open(status_log).read(),
          "pio status reports the device through the live server")
    rc, update_log = phases.run(
        "pio update beside the server", run_pio,
        ["update", "--engine-json", engine_json, "--engine-port", str(port)],
        env, "update.log", 600)
    if report["platform"] == "tpu":
        check(rc == 1 and f"pid {warm['pid']}" in open(update_log).read(),
              "pio update beside a live deploy is refused and names the "
              "server process that holds the chip")
    else:
        check(rc == 0, "pio update runs beside the server (CPU is shared)")
    check(ask(known[1], 5)["itemScores"] != [],
          "the engine server still answers")

    # -- undeploy, then deploy again: a second process, one cache ----------
    undeploy("pio undeploy", srv)
    srv2, again = deploy("pio deploy again", "deploy_again.log")
    pc = again["aot"]["pcache"]
    print(f"[chip_smoke] second deploy process persistent cache: hits="
          f"{pc['hits']} misses={pc['misses']} (AOT warm "
          f"{again['aotWarm']['wallS']}s against "
          f"{warm['aotWarm']['wallS']}s by the first)", flush=True)
    check(again["aotWarm"]["compiled"] == warm["aotWarm"]["compiled"]
          and pc["hits"] >= again["aotWarm"]["compiled"]
          and pc["misses"] == 0,
          f"the second deploy process compiled nothing: all "
          f"{again['aotWarm']['compiled']} warm buckets came out of "
          f"{cache_dir} ({pc['hits']} persistent-cache hits, 0 misses)")
    check(ask(known[2], 5)["itemScores"] != [],
          "the redeployed engine server answers")
    undeploy("pio undeploy again", srv2)
    es.send_signal(signal.SIGTERM)
    check(es.wait(timeout=60) == 0, "the event server stopped (exit 0)")
    default_dir = os.path.join(REPO, ".xla_cache")
    check(cache_dir == default_dir or not os.path.isdir(default_dir)
          or set(os.listdir(default_dir)) == default_before,
          f"every process cached in {cache_dir} and nowhere else "
          f"({len(os.listdir(cache_dir))} entries)")
    return {
        "device": {"platform": report["platform"],
                   "kind": report["device_kind"], "count": n_dev},
        "events": n_events, "ratings": int(len(ratings[0])), "rank": rank,
        "vocabulary": [int(U.shape[0]), int(V.shape[0])],
        "factor_sharding": args.factor_sharding,
        "train_report": report, "row_relerr_vs_f64": row_err,
        "serve": {"aot": aot, "xlaCache": stats["xlaCache"],
                  "maxBatchSize": stats["maxBatchSize"]},
        "device_memory": {"train": report.get("devices", []),
                          "deploy_warm": mem_warm, "after_queries": mem_q},
    }


if __name__ == "__main__":
    main()
