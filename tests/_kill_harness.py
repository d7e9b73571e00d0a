"""Reusable fork-and-kill harness for preemption/resume chaos tests.

A child python process runs a small deterministic training job under
``DurableTrainer``; a scripted fault at the ``"training.step"`` seam
kills it at an EXACT step boundary — ``os._exit`` (hard kill, nothing
drains) or self-``SIGTERM`` (the preemption handler drains the in-flight
window and writes a final snapshot). The parent then resumes from the
same checkpoint directory (fresh process = fresh jit caches, the honest
preemption scenario) and the calling test compares the resumed run's
loss trajectory and final params bit-for-bit against an uninterrupted
reference.

Child protocol: ``python _kill_harness.py '<json config>'``; the child
writes ``result.json`` (iteration/epoch counters, per-iteration scores,
sha256 param digest) into the checkpoint directory on clean completion.

Config keys: checkpoint_dir, total_epochs, frequency,
records_dir (switches the child onto the sharded-record input pipeline:
conv net + shard-shuffled, buffer-shuffled, jit-augmented record
batches — the parent writes the shards with ``write_records`` first),
kill_mode (None | "exit" | "sigterm" | "hang"), kill_at_iteration, seed,
watchdog_s (arms DurableTrainer's StepWatchdog — pair with "hang", which
sleeps forever at the step seam so the watchdog's monitor thread must
notice, dump the flight recorder, and interrupt the hung dispatch).
The flight recorder dumps into checkpoint_dir (DL4JTPU_FLIGHT_DIR is set
before training starts), so the parent can read the black box of a child
that died hung.

FLEET MODE (``mode: "elastic"``): N children form an elastic
bounded-staleness local-SGD fleet over a shared FileCoordinationStore
(``store_dir``), each with its OWN kill plan (``kill_mode`` /
``kill_at_iteration`` per rank — stagger them to script multi-failure
scenarios). ``run_fleet`` spawns the ranks concurrently, optionally
RESTARTS a rank after its first process exits (the preemption-then-
reschedule scenario: the restart restores the newest durable snapshot
and rejoins), and SIGKILLs hang-mode ranks once every other rank
finished — the parent is the cluster scheduler of the chaos story.
Each child writes ``result_<host>.json`` (final digest, agreed flag,
rounds, membership-transition counts) into its checkpoint dir.

SERVING MODE (``mode: "serving"``): each child is an InferenceServer
replica with continuous-batched decode, registered in the shared store
by a ReplicaAgent (serving/fleet.py) — the parent runs a FleetRouter
over the same store and drives Poisson load while per-replica kill
plans SIGTERM or hang a replica at an exact decode-dispatch count
(the ``"serving.decode_step"`` seam, so the kill lands MID-DECODE with
partial output in flight). Children serve until the parent publishes
``ctl/stop``, then drain, deregister, and write ``result_<host>.json``
(responses by code, shed, drain + heartbeat counters). ``run_fleet``
reclaims hang-mode replicas exactly like hang-mode trainers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HARNESS = os.path.abspath(__file__)

# deterministic toy problem shared by child and reference runs
N_BATCHES = 6
BATCH = 8
FEATURES = 5
CLASSES = 3


def build_net(seed: int = 7):
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(seed).updater("adam")
            .learning_rate(0.01).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=CLASSES, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(FEATURES)).build())
    return MultiLayerNetwork(conf).init()


def build_iterator(seed: int = 7):
    import numpy as np
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N_BATCHES * BATCH, FEATURES)).astype(np.float32)
    y = np.eye(CLASSES, dtype=np.float32)[
        rng.integers(0, CLASSES, N_BATCHES * BATCH)]
    return ListDataSetIterator(
        [DataSet(x[i * BATCH:(i + 1) * BATCH], y[i * BATCH:(i + 1) * BATCH])
         for i in range(N_BATCHES)], batch_size=BATCH)


# ----------------------------------------------------------------------
# records mode: sharded-record pipeline + jit augmentation under kill
# ----------------------------------------------------------------------
# A records_dir in the config switches the child onto the full input
# pipeline: uint8 image records in 3 shards, epoch-seeded shard shuffle,
# a shuffle buffer, and the jitted crop/flip/normalize augmentation —
# so the kill/resume proof covers the pipeline cursor AND the
# counter-derived augmentation rng, not just a list iterator's index.

REC_SHARDS = 3
REC_IMAGE = 3           # [3, 3, 1] uint8 images


def build_conv_net(seed: int = 7):
    """Tiny conv net matching the records' image shape (the dense
    build_net expects flat features; augmentation needs NHWC)."""
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer,
                                                   GlobalPoolingLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(seed).updater("adam")
            .learning_rate(0.01).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(2, 2),
                                    border_mode="same", activation="relu"))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=CLASSES, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(REC_IMAGE, REC_IMAGE, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def write_records(records_dir: str, seed: int = 7):
    import numpy as np
    from deeplearning4j_tpu.data.records import write_shard_set

    rng = np.random.default_rng(seed)
    n = N_BATCHES * BATCH
    imgs = rng.integers(0, 256, (n, REC_IMAGE, REC_IMAGE, 1),
                        dtype=np.uint8)
    labels = np.eye(CLASSES, dtype=np.float32)[
        rng.integers(0, CLASSES, n)]
    return write_shard_set(
        records_dir, "toy",
        [{"features": imgs[i], "labels": labels[i]} for i in range(n)],
        REC_SHARDS)


def build_records_iterator(records_dir: str, seed: int = 7):
    from deeplearning4j_tpu.data.pipeline import (Augment,
                                                  RecordDataSetIterator)

    return RecordDataSetIterator(
        records_dir, "toy", batch_size=BATCH, seed=seed,
        shuffle_shards=True, shuffle_buffer=12,
        augment=Augment(crop_pad=1, flip=True, scale=1 / 255.0,
                        mean=(0.5,), std=(0.25,)))


def params_sha(net) -> str:
    import hashlib
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(net.params)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _child_env():
    repo_root = os.path.dirname(os.path.dirname(HARNESS))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the virtual 8-device mesh of the test process is pointless here
    # and slows child startup; elastic hosts are single-device
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return repo_root, env


def run_child(config: dict, timeout: float = 120.0):
    """Spawn the harness as a subprocess; returns (returncode, stderr)."""
    repo_root, env = _child_env()
    if "mode" not in config:
        env["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "")
    proc = subprocess.run(
        [sys.executable, HARNESS, json.dumps(config)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=repo_root)
    return proc.returncode, proc.stderr


# ----------------------------------------------------------------------
# fleet mode: N elastic hosts with per-rank kill plans
# ----------------------------------------------------------------------

def elastic_fleet_configs(n: int, store_dir: str, base_dir: str, *,
                          rounds: int = 4, steps_per_round: int = 2,
                          max_staleness: int = 1, lease_s: float = 1.0,
                          evict_after_s: float = None, seed: int = 7,
                          kill_plans: dict = None,
                          watchdog_s: float = None,
                          traceparent: str = None) -> list:
    """One config dict per rank. ``kill_plans`` maps rank ->
    {"kill_mode": ..., "kill_at_iteration": ...} (iteration counts LOCAL
    steps on that rank; the "training.step" seam fires before each).
    ``traceparent`` (a tracing.inject() string) becomes every child's
    DL4JTPU_TRACEPARENT: all hosts' round spans join ONE fleet trace,
    and each child exports trace_<host>.jsonl into its checkpoint dir
    for the timeline collector."""
    fleet = [f"h{i}" for i in range(n)]
    out = []
    for i, host in enumerate(fleet):
        cfg = {
            "mode": "elastic", "fleet": fleet, "host": host,
            "store_dir": store_dir,
            "checkpoint_dir": os.path.join(base_dir, host),
            "rounds": rounds, "steps_per_round": steps_per_round,
            "max_staleness": max_staleness, "lease_s": lease_s,
            "evict_after_s": evict_after_s, "seed": seed,
            "watchdog_s": watchdog_s,
            "traceparent": traceparent,
        }
        cfg.update((kill_plans or {}).get(i, {}))
        out.append(cfg)
    return out


def spawn_fleet_child(config: dict) -> "subprocess.Popen":
    """Fleet children log to a FILE, not a pipe: nobody reads until the
    child exits, and a child that outwrites the pipe buffer blocks for
    good (XLA's CPU backend logs a ~3 KB line per program it loads from a
    warm persistent compile cache — enough to wedge a replica in its
    warm-up). Read it back with :func:`reap`."""
    import tempfile
    repo_root, env = _child_env()
    err = tempfile.TemporaryFile(mode="w+")
    proc = subprocess.Popen(
        [sys.executable, HARNESS, json.dumps(config)],
        stdout=subprocess.DEVNULL, stderr=err, env=env, cwd=repo_root)
    proc.stderr_file = err
    return proc


def reap(proc) -> str:
    """Wait for a :func:`spawn_fleet_child` process; returns its stderr."""
    proc.wait()
    with proc.stderr_file as f:
        f.seek(0)
        return f.read()


def fleet_result(config: dict):
    """The result_<host>.json a fleet child wrote, or None."""
    path = os.path.join(config["checkpoint_dir"],
                        f"result_{config['host']}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def run_fleet(configs: list, *, timeout: float = 300.0,
              restarts: dict = None, restart_delay_s: float = 0.0,
              poll_s: float = 0.2) -> dict:
    """Run an elastic fleet to completion under a hard deadline.

    ``restarts`` maps host id -> replacement config: when that host's
    first process EXITS (clean preemption drain or hard kill alike), the
    replacement spawns ``restart_delay_s`` later — hold it past the
    lease so the survivors OBSERVE the dropout (evict -> rejoin
    transitions) instead of racing the reschedule. Hang-mode
    ranks never exit on their own; once every other rank is done they
    are SIGKILLed (the cluster reclaiming a wedged machine). Returns
    {host: {"rc": int, "stderr": str, "result": dict|None,
    "restarted": bool}}; raises TimeoutError past ``timeout`` (all
    children are killed first — a protocol deadlock must fail fast, not
    eat the suite's budget)."""
    import time as _time
    restarts = dict(restarts or {})
    by_host = {c["host"]: c for c in configs}
    procs = {c["host"]: spawn_fleet_child(c) for c in configs}
    hang_hosts = {c["host"] for c in configs
                  if c.get("kill_mode") == "hang"}
    out = {h: {"rc": None, "stderr": "", "restarted": False}
           for h in procs}
    deadline = _time.monotonic() + timeout
    due: dict = {}          # host -> (config, spawn_at)
    try:
        while True:
            for h, p in list(procs.items()):
                rc = p.poll()
                if rc is None or out[h]["rc"] is not None:
                    continue
                out[h]["rc"] = rc
                out[h]["stderr"] += reap(p)
                if h in restarts:
                    due[h] = (restarts.pop(h),
                              _time.monotonic() + restart_delay_s)
            for h, (cfg, at) in list(due.items()):
                if _time.monotonic() >= at:
                    del due[h]
                    procs[h] = spawn_fleet_child(cfg)
                    by_host[h] = cfg
                    out[h] = {"rc": None, "stderr": out[h]["stderr"],
                              "restarted": True}
            pending = [h for h, p in procs.items() if p.poll() is None]
            if not pending and not due:
                break
            if set(pending) <= hang_hosts and not restarts and not due:
                # only wedged ranks left: reclaim them
                for h in pending:
                    procs[h].kill()
                    out[h]["rc"] = "killed_hung"
                    out[h]["stderr"] += reap(procs[h])
                break
            if _time.monotonic() > deadline:
                for h in pending:
                    procs[h].kill()
                    reap(procs[h])
                raise TimeoutError(
                    f"fleet did not finish within {timeout}s; still "
                    f"running: {pending}")
            _time.sleep(poll_s)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                reap(p)
    for h in out:
        out[h]["result"] = fleet_result(by_host[h])
    return out


def elastic_batch_fn(seed: int, host_index: int):
    """Per-host data schedule as a PURE function of (round, step) —
    process-restart-stable (no python hash salting, no iterator state),
    which is what makes rejoin replay bit-exact."""
    import numpy as np

    def fn(round_, step):
        s = (int(seed) * 1000003 + host_index * 10007
             + int(round_) * 101 + int(step)) % (2 ** 31)
        rng = np.random.default_rng(s)
        x = rng.normal(size=(BATCH, FEATURES)).astype(np.float32)
        y = np.eye(CLASSES, dtype=np.float32)[
            rng.integers(0, CLASSES, BATCH)]
        return x, y
    return fn


# ----------------------------------------------------------------------
# serving mode: N decode replicas + router-driven chaos
# ----------------------------------------------------------------------

SERVE_VOCAB = 32
SERVE_WINDOW = 32       # page_size 8 × pages_per_seq 4


def build_lm_net(seed: int = 7):
    """Tiny decode-capable transformer shared by every serving child and
    the parent-side router tests — small enough that three replicas warm
    their bucket ladders concurrently on one core inside the budget."""
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    conf = transformer_lm(SERVE_VOCAB, n_layers=1, d_model=32, n_heads=2,
                          d_ff=64, seed=seed, input_ids=True,
                          max_cache_t=SERVE_WINDOW)
    return ComputationGraph(conf).init()


def serving_fleet_configs(n: int, store_dir: str, base_dir: str, *,
                          lease_s: float = 1.0,
                          request_timeout_s: float = 30.0,
                          run_s: float = 120.0, seed: int = 7,
                          kill_plans: dict = None) -> list:
    """One config per replica. ``kill_plans`` maps index ->
    {"kill_mode": "sigterm"|"hang", "kill_at_dispatch": N} — N counts
    DECODE-phase dispatches on that replica (prefills excluded), so the
    kill is guaranteed to land mid-decode with tokens already emitted."""
    out = []
    for i in range(n):
        host = f"r{i}"
        cfg = {"mode": "serving", "host": host, "store_dir": store_dir,
               "checkpoint_dir": os.path.join(base_dir, host),
               "lease_s": lease_s,
               "request_timeout_s": request_timeout_s,
               "run_s": run_s, "seed": seed}
        cfg.update((kill_plans or {}).get(i, {}))
        out.append(cfg)
    return out


def _serving_child_main(config: dict) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")

    import signal
    import time

    from deeplearning4j_tpu.parallel.elastic import FileCoordinationStore
    from deeplearning4j_tpu.serving import InferenceServer, ReplicaAgent
    from deeplearning4j_tpu.util import faults
    from deeplearning4j_tpu.util import metrics as _metrics
    from deeplearning4j_tpu.util import tracing as _tracing

    directory = config["checkpoint_dir"]
    os.makedirs(directory, exist_ok=True)
    os.environ["DL4JTPU_FLIGHT_DIR"] = directory
    if config.get("traceparent"):
        os.environ["DL4JTPU_TRACEPARENT"] = config["traceparent"]

    replica = config["host"]
    store = FileCoordinationStore(config["store_dir"])
    registry = _metrics.REGISTRY
    tracer = _tracing.Tracer(host=replica, registry=registry)
    server = InferenceServer(
        build_lm_net(config.get("seed", 7)),
        tracer=tracer, registry=registry,
        decode={"max_batch": 2, "page_size": 8, "pages_per_seq": 4,
                "prefill_chunk": 8,
                "request_timeout_s": config.get("request_timeout_s",
                                                30.0)},
        warmup_background=True)
    # registration happens BEFORE the warmup finishes: the replica is
    # visible (ready=false) while the bucket ladder compiles, and the
    # router's readiness gate keeps traffic away until it flips
    agent = ReplicaAgent(server, store, replica=replica,
                         lease_s=config.get("lease_s", 1.0),
                         registry=registry).start()

    plan = faults.FaultPlan()
    kill_mode = config.get("kill_mode")
    kill_at = config.get("kill_at_dispatch")
    if kill_mode:
        state = {"n": 0}

        def kill(payload):
            if payload.get("phase") == "prefill":
                return
            state["n"] += 1
            if state["n"] == kill_at:
                if kill_mode == "hang":
                    # wedge INSIDE the dispatch, dispatch lock held: the
                    # agent's step-boundary probe now fails, heartbeats
                    # stop, and the lease lapses — the hang is visible
                    # to the fleet precisely because liveness is
                    # attested, not assumed
                    time.sleep(600)
                    return
                os.kill(os.getpid(), signal.SIGTERM)
        plan.always("serving.decode_step", exc=kill)

    deadline = time.monotonic() + config.get("run_s", 120.0)
    with plan.active():
        while time.monotonic() < deadline:
            if store.get("ctl/stop") is not None:
                break
            time.sleep(0.1)
        agent.stop(deregister=True)
        server.stop(drain=True, timeout=10.0)

    try:
        tracer.export_jsonl(os.path.join(directory,
                                         f"trace_{replica}.jsonl"))
    except Exception:
        pass
    responses = {}
    resp = registry.get("serving_responses_total")
    if resp is not None:
        for s in resp.snapshot()["series"]:
            responses[s["labels"]["code"]] = s["value"]

    def _ctr(name, **labels):
        m = registry.get(name)
        return m.value(**labels) if m is not None else 0.0

    result = {
        "host": replica,
        "served": server.served,
        "shed": server.shed,
        "responses": responses,
        "heartbeats_published": _ctr("fleet_heartbeats_total",
                                     result="published"),
        "drain_ok": _ctr("serving_drain_total", result="ok"),
        "drain_timeout": _ctr("serving_drain_total", result="timeout"),
    }
    with open(os.path.join(directory, f"result_{replica}.json"), "w") as f:
        json.dump(result, f)


def _install_kill_plan(plan, config) -> None:
    """Per-rank kill plan on the shared "training.step" seam: the seam
    fires BEFORE dispatching the (iteration+1)-th local step."""
    import signal

    kill_mode = config.get("kill_mode")
    kill_at = config.get("kill_at_iteration")
    if not kill_mode:
        return

    def kill(payload):
        if payload["iteration"] == kill_at:
            if kill_mode == "exit":
                os._exit(9)
            if kill_mode == "hang":
                import time
                time.sleep(600)
                return
            os.kill(os.getpid(), signal.SIGTERM)
    plan.always("training.step", exc=kill)


def _elastic_child_main(config: dict) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from deeplearning4j_tpu.util import faults
    from deeplearning4j_tpu.util import metrics as _metrics
    from deeplearning4j_tpu.parallel.elastic import (ElasticConfig,
                                                     ElasticTrainer)

    directory = config["checkpoint_dir"]
    os.makedirs(directory, exist_ok=True)
    os.environ["DL4JTPU_FLIGHT_DIR"] = directory
    if config.get("traceparent"):
        os.environ["DL4JTPU_TRACEPARENT"] = config["traceparent"]

    host = config["host"]
    fleet = tuple(config["fleet"])
    cfg = ElasticConfig(
        fleet=fleet, host=host,
        steps_per_round=config.get("steps_per_round", 2),
        max_staleness=config.get("max_staleness", 1),
        lease_s=config.get("lease_s", 1.0),
        evict_after_s=config.get("evict_after_s"),
        poll_s=config.get("poll_s", 0.05))
    trainer = ElasticTrainer(
        build_net(config.get("seed", 7)), config["store_dir"], cfg,
        checkpoint_dir=directory, handle_signals=True,
        watchdog_s=config.get("watchdog_s"))

    plan = faults.FaultPlan()
    _install_kill_plan(plan, config)

    batch_fn = elastic_batch_fn(config.get("seed", 7),
                                fleet.index(host))
    error = None
    try:
        with plan.active():
            trainer.fit(batch_fn, rounds=config["rounds"])
    except Exception as e:       # report protocol errors via result.json
        error = f"{type(e).__name__}: {e}"

    # per-host span export for the timeline collector (best-effort: a
    # hard-killed child leaves only its store-side trace records)
    trace_id = None
    try:
        trainer.tracer.export_jsonl(
            os.path.join(directory, f"trace_{host}.jsonl"))
        fits = trainer.tracer.find("elastic.fit")
        if fits:
            trace_id = fits[-1].trace_id
    except Exception:
        pass

    from deeplearning4j_tpu.util import flightrecorder as _flight
    reg = _metrics.REGISTRY
    transitions = {}
    ctr = reg.get("membership_transitions_total")
    if ctr is not None:
        for s in ctr.snapshot()["series"]:
            key = f"{s['labels']['event']}:{s['labels']['host']}"
            transitions[key] = s["value"]
    rounds_hist = reg.get("sync_round_seconds")
    result = {
        "host": host,
        "round": trainer._round,
        "final_digest": trainer.final_digest,
        "agreed": trainer.agreed,
        "resumed": trainer.resumed,
        "preempted": trainer.preempted,
        "incarnation": trainer.coord.incarnation,
        "iteration_count": getattr(trainer.net, "iteration_count", 0),
        "transitions": transitions,
        "sync_rounds_total": (reg.get("sync_rounds_total").value(host=host)
                              if reg.get("sync_rounds_total") else 0),
        "sync_round_seconds_sum": (rounds_hist.sum(host=host)
                                   if rounds_hist else 0.0),
        "sync_round_seconds_count": (rounds_hist.count(host=host)
                                     if rounds_hist else 0),
        # stall/evict attribution straight from the flight recorder, so
        # the parent can assert WHICH host stalled a round
        "stalls": [{"round": e.get("round"),
                    "waiting_on": e.get("waiting_on")}
                   for e in _flight.events("elastic_stall")],
        "evictions": [{"host": e.get("host"),
                       "effective_round": e.get("effective_round"),
                       "trace_id": e.get("trace_id")}
                      for e in _flight.events("elastic_evict")],
        # lease-level evict/rejoin observations with the trace they were
        # recorded under (the observer's active round span)
        "membership_events": [{"event": e.get("event"),
                               "host": e.get("host"),
                               "trace_id": e.get("trace_id")}
                              for e in _flight.events(
                                  "elastic_membership")],
        "trace_id": trace_id,
        "error": error,
    }
    with open(os.path.join(directory, f"result_{host}.json"), "w") as f:
        json.dump(result, f)
    if error is not None:
        sys.exit(3)


def _child_main(config: dict) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)   # match the test processes

    from deeplearning4j_tpu.util import faults
    from deeplearning4j_tpu.util.durable import DurableTrainer

    directory = config["checkpoint_dir"]
    # the black box lands next to the checkpoints, where the parent looks
    os.environ["DL4JTPU_FLIGHT_DIR"] = directory

    records_dir = config.get("records_dir")
    net = (build_conv_net(config.get("seed", 7)) if records_dir
           else build_net(config.get("seed", 7)))
    trainer = DurableTrainer(
        net, directory,
        frequency=config.get("frequency", 2), handle_signals=True,
        async_writes=config.get("async", True),
        watchdog_s=config.get("watchdog_s"))

    scores = []

    class _Collect:
        def iteration_done(self, model, iteration, score):
            scores.append(float(score))

        def on_epoch_start(self, *a):
            pass

        def on_epoch_end(self, *a):
            pass

        def on_forward_pass(self, *a):
            pass

        def on_gradient_calculation(self, *a):
            pass

        def on_backward_pass(self, *a):
            pass

    trainer.net.add_listener(_Collect())

    # the seam fires BEFORE dispatching the (iteration+1)-th step:
    # iterations 1..kill_at are applied, nothing after ("exit" hard-kills
    # with nothing draining; "hang" wedges so only a watchdog monitor
    # thread or a peer's lease can notice)
    plan = faults.FaultPlan()
    _install_kill_plan(plan, config)

    data = (build_records_iterator(records_dir, config.get("seed", 7))
            if records_dir else build_iterator(config.get("seed", 7)))
    with plan.active():
        trainer.fit(data, epochs=config["total_epochs"])

    result = {
        "iteration_count": trainer.net.iteration_count,
        "epoch_count": trainer.net.epoch_count,
        "preempted": trainer.preempted,
        "resumed": trainer.resumed,
        "scores": scores,
        "params_sha": params_sha(trainer.net),
    }
    with open(os.path.join(directory, "result.json"), "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    _config = json.loads(sys.argv[1])
    if _config.get("mode") == "elastic":
        _elastic_child_main(_config)
    elif _config.get("mode") == "serving":
        _serving_child_main(_config)
    else:
        _child_main(_config)
