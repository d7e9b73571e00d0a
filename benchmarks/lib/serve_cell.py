"""Runner of the serving cells: ``InferenceServer(net, decode={...})`` and
``server.decode.submit(...)``, the call the HTTP handler makes.

A closed loop: one client thread for each lane, each walking its own list
from the traffic file and sending the next request when the last is
answered. Clients start in set-up, staggered, and run on until after the
window has closed, so the engine is saturated at both edges. The window
opens once every client has had its first answer, and both its edges wait
for the engine's next delivery of decoded tokens, so it holds whole blocks
(as the training window holds whole steps). Generated tokens are
counted where they are delivered: the engine's decode-token counter at the
two edges plus the first tokens stamped inside, so a request in flight at
an edge gives what it delivered inside and nothing else. Tails are taken
over every request submitted and completed inside the window.

After the window a sample of the finished requests, drawn from the seed
with the longest in it, is checked against the plain reference: one forward
pass over each prompt with its served tokens, and the widest gap by which a
served token's logit lies below the reference's best. A configuration that
states ``limits.logit_gap_outlier_share`` is held to that share of the
compared tokens lying beyond ``logit_gap``, not to the widest gap: a model
with a discrete choice inside (the top k of some scores) differs from its
reference by rounding everywhere and, where two scores lie closer than the
rounding of their input, by the other choice at a few tokens, which is no
fault (PERF.md section 2).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from . import common, compare, control, traffic, weights
from .readers import percentile
from .train_cell import build_net


class Clients:
    def __init__(self, sched, mix, vocab, seed, timeout_s):
        self.sched, self.mix, self.vocab, self.seed = sched, mix, vocab, seed
        self.timeout_s = timeout_s
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.records = []           # (client, serial, row, ids, req or None)
        self.first_done = [threading.Event() for _ in mix["schedule"]]
        self.threads = [threading.Thread(target=self._run, args=(c,),
                                         daemon=True, name=f"client{c}")
                        for c in range(len(mix["schedule"]))]

    def start(self):
        for th in self.threads:
            th.start()
            time.sleep(self.mix.get("stagger_s", 0.0))

    def _run(self, c):
        for serial, row in traffic.requests(self.mix, c):
            if self.stop.is_set():
                return
            ids = traffic.prompt_ids(self.mix, self.vocab, self.seed, c,
                                     serial, row)
            t = time.monotonic()
            try:
                req = self.sched.submit(ids, max_new_tokens=row[1],
                                        temperature=0.0, eos_id=None,
                                        timeout_s=self.timeout_s)
            except Exception as e:  # noqa: BLE001 - a refusal is a failure
                with self.lock:
                    self.records.append((c, serial, row, ids, None, t,
                                         repr(e)))
                time.sleep(0.05)
                continue
            req.wait(self.timeout_s + 5.0)
            with self.lock:
                self.records.append((c, serial, row, ids, req, t, None))
            self.first_done[c].set()

    def finish(self, timeout_s=120.0):
        self.stop.set()
        for th in self.threads:
            th.join(timeout_s)
        return not any(th.is_alive() for th in self.threads)


def summarise(records, t0, t1):
    """Per-request rows for everything submitted and finished inside
    [t0, t1]; ``failed`` counts refusals and requests that did not run to
    their stated length."""
    rows, failed = [], 0
    for c, serial, row, ids, req, t_sub, err in records:
        if req is None:
            if t0 <= t_sub <= t1:
                failed += 1
                rows.append({"ok": False, "ttft_s": float("inf"),
                             "tpot_s": float("inf"), "prompt_tokens": row[0],
                             "prefix_covered_tokens": 0})
            continue
        if not (req.t_submit >= t0 and req.t_done is not None
                and req.t_done <= t1):
            continue
        ok = (req.finish_reason == "max_tokens"
              and len(req.tokens) == row[1])
        failed += 0 if ok else 1
        n = len(req.tokens)
        rows.append({
            "ok": ok, "client": c, "serial": serial,
            "prompt_tokens": int(len(ids)), "output_tokens": n,
            "prefix_covered_tokens": int(req.prefix_covered_tokens),
            "ttft_s": (req.t_first_token - req.t_submit) if ok
            else float("inf"),
            "tpot_s": ((req.t_done - req.t_first_token) / (n - 1))
            if ok and n > 1 else (None if ok else float("inf")),
            "ids": ids, "tokens": list(req.tokens)})
    return rows, failed


def attended_keys(rows) -> float:
    """(token, key) pairs the engine computed for the finished requests:
    every fed position p past the prefix-cache cover attends p + 1 keys;
    the last generated token is never fed."""
    total = 0.0
    for r in rows:
        if not r["ok"]:
            continue
        lo = min(r["prefix_covered_tokens"], r["prompt_tokens"] - 1)
        hi = r["prompt_tokens"] + r["output_tokens"] - 1     # exclusive
        total += (hi * (hi + 1) - lo * (lo + 1)) / 2.0
    return total


def draw_sample(rows, seed, want_tokens, most=16):
    """Finished requests for the reference: the longest, one that hit the
    prefix cache and one that missed where there are such, then more drawn
    from the seed until ``want_tokens`` served tokens are in."""
    ok = [r for r in rows if r["ok"]]
    if not ok:
        return []
    size = lambda r: r["prompt_tokens"] + r["output_tokens"]   # noqa: E731
    picked = [max(ok, key=size)]
    for want_hit in (True, False):
        kind = [r for r in ok if (r["prefix_covered_tokens"] > 0) == want_hit
                and r not in picked]
        if kind and not any((p["prefix_covered_tokens"] > 0) == want_hit
                            for p in picked):
            picked.append(kind[0])
    rest = [r for r in ok if r not in picked]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    for i in order:
        if (sum(p["output_tokens"] for p in picked) >= want_tokens
                or len(picked) >= most):
            break
        picked.append(rest[i])
    return picked


def reference_logits(ref, cfg, flat, sample, mode="f32"):
    """For each sampled request the reference's logits at the positions of
    its served tokens (one forward over prompt + served tokens, padded to
    a few fixed lengths; causal, so the padding changes nothing)."""
    pad = cfg.get("reference_pad", 512)
    out = []
    for r in sample:
        ids = np.concatenate([r["ids"], np.asarray(r["tokens"][:-1],
                                                   np.int32)])
        first = r["prompt_tokens"] - 1
        positions = np.arange(first, first + r["output_tokens"])
        padded = np.zeros(-(-len(ids) // pad) * pad, np.int32)
        padded[:len(ids)] = ids
        z = ref.logits_at(flat, padded, positions, cfg=cfg, mode=mode,
                          q_block=pad)
        out.append(np.asarray(z))
    return out


def wait_for_delivery(registries, timeout_s: float = 10.0) -> bool:
    """Returns at the instant the engine's count of decoded tokens next
    moves (looked at every millisecond), or after ``timeout_s``."""
    def count():
        return common.read_stat(registries, TOKENS["metric"],
                                TOKENS["labels"])
    start, deadline = count(), time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if count() != start:
            return True
        time.sleep(0.001)
    return False


def run(cell: dict, cfg: dict, mix: dict, args, env: dict) -> dict:
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.util import metrics

    family = env["family"]
    eng = dict(cfg["engine"], **env.get("engine_override", {}))
    window_tokens = eng["page_size"] * eng["pages_per_seq"]
    parts = {"imports": common.process_age_s()}
    net = build_net(family, cfg, args.seed, max_cache_t=window_tokens)
    parts["build_net"] = common.process_age_s()
    if env.get("plant") is not None:          # tests plant faults here
        env["plant"](net)
    timeout_s = float(cfg.get("request_timeout_s", 600.0))
    server = InferenceServer(net, decode=dict(
        eng, request_timeout_s=timeout_s,
        max_queue=4 * len(mix["schedule"])))
    sched = server.decode
    parts["server_and_ladder"] = common.process_age_s()
    registries = [server.registry, metrics.REGISTRY]
    edges = common.Edges(registries, env["wants"] + family.WANTS
                         + [TOKENS, PREFILL_TOKENS])
    sampler = common.GaugeSampler(registries, env["gauges"])
    clients = Clients(sched, mix, cfg["vocab_size"], args.seed, timeout_s)
    tracer = None
    if env["tracing"]:
        tracer = common.TraceSlice(env["trace_dir"],
                                   *common.trace_plan(args.seconds))
    try:
        clients.start()
        for ev in clients.first_done:
            if not ev.wait(timeout_s):
                raise SystemExit("benchmark: a client got no first answer")
        compiles0 = env["compiles"].count
        sampler.start()
        # both edges stand where the engine has just delivered a block of
        # tokens, so the window holds whole blocks: a block is up to 64
        # tokens at once, 1.7 % of a chat window, wherever an edge cuts it
        wait_for_delivery(registries)
        edges.open()
        setup_s = common.process_age_s()
        t0 = time.monotonic()
        if tracer:
            tracer.start()
        time.sleep(args.seconds)
        wait_for_delivery(registries)
        t1 = time.monotonic()
        edges.close()
        compiles = env["compiles"].count - compiles0
        sampler.stop()
        all_back = clients.finish()
        if tracer:
            tracer.join()
            if tracer.error:
                raise tracer.error
        peak = common.memory_peak_bytes()
    finally:
        clients.stop.set()
        server.stop(drain=False)

    window_s = t1 - t0
    rows, failed = summarise(clients.records, t0, t1)
    first_tokens = sum(1 for rec in clients.records if rec[4] is not None
                       and rec[4].t_first_token is not None
                       and t0 <= rec[4].t_first_token <= t1)
    decoded = edges.delta(TOKENS) or 0.0
    generated = decoded + first_tokens
    computed = decoded + (edges.delta(PREFILL_TOKENS) or 0.0)
    good = [r for r in rows if r["ok"]]
    ttft = [r["ttft_s"] for r in rows]
    tpot = [r["tpot_s"] for r in rows if r["tpot_s"] is not None]
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": generated / window_s,
           # the decode blocks' own tokens, without the prefill's first ones
           "decode_tokens_per_s": decoded / window_s}
    if len(rows) >= 2:
        e2e["ttft_p90_ms"] = 1000.0 * percentile(ttft, 90)
        e2e["tpot_p90_ms"] = 1000.0 * percentile(tpot, 90)
    out = {"attempted": len(rows), "failed": failed, "window_s": window_s,
           "requests": good, "setup_s": setup_s, "chips": cell["chips"],
           "memory_peak_bytes": peak, "compiles_in_window": compiles,
           "edges": edges, "gauge_peaks": sampler.peak,
           "flops": family.serve_flops(cfg, {
               "computed_tokens": computed,
               "attended_keys": attended_keys(rows),
               "deltas": [edges.delta(w) for w in family.WANTS]}),
           "generated_tokens": generated, "end_to_end": e2e}

    want_tokens = cfg.get("check_tokens", 400)
    sample = draw_sample(rows, args.seed, want_tokens)
    del server, sched, net, clients
    common.free_device_memory()
    t_ref = time.perf_counter()
    ref = common.load_reference(cfg)
    flat = weights.make_weights(family, cfg, args.seed)
    logits = reference_logits(ref, cfg, flat, sample)
    gaps = [g for z, r in zip(logits, sample)
            for g in compare.token_gaps(z, r["tokens"])]
    out["reference_s"] = time.perf_counter() - t_ref
    control_gaps = None
    if env.get("control_mode"):      # tests/chip_control.py, never a run
        control_gaps = control.lower_precision_gaps(
            logits, reference_logits(ref, cfg, flat, sample,
                                     env["control_mode"]))
    verdict = compare.Verdict()
    verdict.require("every_client_returned", all_back)
    verdict.require("no_compile_in_window", compiles == 0, f"{compiles}")
    verdict.require("requests_ran_to_length", failed == 0 and len(good) > 0,
                    f"{failed} failed of {len(rows)}")
    limits = cfg["limits"]
    note = (f"{len(gaps)} tokens of {len(sample)} requests, "
            f"{sum(1 for r in sample if r['prefix_covered_tokens'])} "
            "of them prefix-cache hits")
    gaps_max = max(gaps) if gaps else None
    gaps_p99 = float(np.percentile(gaps, 99)) if gaps else None
    share = compare.outlier_share(gaps, limits["logit_gap"])
    share_limit = limits.get("logit_gap_outlier_share")
    if share_limit is None:
        verdict.add("served_token_logit_gap_max", gaps_max,
                    limits["logit_gap"], note)
    else:
        verdict.add("served_token_gap_outlier_share", share, share_limit,
                    f"{note}; beyond {limits['logit_gap']}; largest gap "
                    f"{gaps_max}, 99th percentile {gaps_p99}")
        # a share over few tokens is noise
        verdict.require("tokens_compared", len(gaps) >= want_tokens,
                        f"{len(gaps)} of {want_tokens}")
    out["verdict"] = verdict
    out["readings"] = {"gaps_max": gaps_max, "gaps_p99": gaps_p99,
                       "gaps_outlier_share": share, "n_tokens": len(gaps),
                       "n_requests": len(sample),
                       "completed": len(good), "setup_parts_s": parts,
                       "end_to_end": e2e,
                       "ttft_p50_ms": 1000.0 * percentile(ttft, 50)
                       if ttft else None,
                       "tpot_p50_ms": 1000.0 * percentile(tpot, 50)
                       if tpot else None,
                       "control_gaps_max": max(control_gaps)
                       if control_gaps else None,
                       "control_outlier_share": compare.outlier_share(
                           control_gaps or [], limits["logit_gap"])}
    out["sample"] = sample
    return out


TOKENS = {"metric": "decode_tokens_total", "labels": {"phase": "decode"},
          "stat": "value"}
PREFILL_TOKENS = {"metric": "decode_tokens_total",
                  "labels": {"phase": "prefill"}, "stat": "value"}
