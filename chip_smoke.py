#!/usr/bin/env python3
"""chip_smoke.py: does the main path still start on the chip?

Trains and then serves ONE transformer LM at the flagship width (d_model 768,
12 layers, 12 heads, d_ff 3072, vocabulary 32768, mixed bf16; random weights
from a seed) through the entry points a user calls, in one process that owns
the chip from start to end:

  kernel  the Pallas flash forward/backward against the XLA path on a small
          input at the flagship head shape, and at the shape of the
          benchmark's training cell ([1, 8192, 32, 64], one backward call)
  train   ``ComputationGraph(...).init()`` then ``net.fit(iterator)`` at
          T=4096, where attention routes itself to the Pallas kernel
  serve   ``InferenceServer(net, decode={...})`` (fused block path) answering
          concurrent ``POST /generate`` over HTTP, same params
  dp4/sp4 with >= 4 devices: the same net data-parallel over all of them, and
          sequence-parallel with ring attention through the Pallas hop route

Any failed check, exception or logged-and-swallowed error (an ``error``
finish, a ``decode_error`` flight event, a non-finite loss) is a non-zero
exit. Without a TPU it exits non-zero before doing anything else and prints
no result. Once the legs have run, the last two lines of stdout are
``summary: {...}`` (the legs with their checks and times, peak HBM, compile
cache hits, ``"claim": null``) and the result, one JSON object with exactly
these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}``.

``--small`` runs every leg at a toy size on whatever backend JAX has, with
the kernels in interpret mode because the switch asks for it; it exists to
debug this script on a CPU and proves nothing about the chip:
``JAX_PLATFORMS=cpu python chip_smoke.py --small``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 21
FULL = dict(vocab=32768, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
            train_t=4096, train_batch=2, train_steps=6, kernel_t=1024,
            kernel_cell=(1, 8192, 32, 64),
            page_size=16, pages_per_seq=32, lanes=8, block_len=8,
            prompt_lens=(9, 20, 47, 100, 180, 300), new_tokens=24,
            sp_t=4096)
SMALL = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
             train_t=128, train_batch=2, train_steps=4, kernel_t=128,
             kernel_cell=(1, 256, 4, 32),
             page_size=4, pages_per_seq=8, lanes=2, block_len=4,
             prompt_lens=(3, 5, 9, 14, 3, 7), new_tokens=6, sp_t=512)


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)
    print(f"    ok: {what}", flush=True)


def compile_seconds(registry) -> float:
    """Wall the guarded jit sites of this registry have spent compiling so
    far (``xla_compile_seconds``, util/xla.py)."""
    hist = registry.get("xla_compile_seconds")
    return 0.0 if hist is None else hist.total_sum()


def retraces(registry, fn=None) -> float:
    c = registry.get("jit_retraces_total")
    if c is None:
        return 0.0
    return c.value(fn=fn) if fn is not None else c.total()


# --------------------------------------------------------------------------
# legs
# --------------------------------------------------------------------------


def flash_parity(shape, what: str) -> dict:
    """Pallas flash fwd + bwd against the XLA path on one [b, t, h, d]
    bf16 causal input; the three gradients' gaps are printed and held to
    a few bf16 ulps at the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import dot_product_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    b, t, h, d = shape
    keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               .astype(jnp.bfloat16) for kk in keys)

    def grads(attn):
        def loss(q, k, v):
            return jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    # the reference is the XLA path in float32 on the same (bf16-rounded)
    # values, a few heads at a call so that its [b, heads, t, t] scores
    # stay under half a GiB (the loss is a sum over heads); the flag says
    # 0 while it is traced, so at no length can it route to the kernel.
    # flash_attention() IS the kernel, compiled unless --small asked for
    # interpret mode
    group = max(1, min(h, (1 << 27) // (b * t * t)))
    while h % group:
        group -= 1
    ref_fn = grads(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True))
    prior = os.environ.pop("DL4JTPU_FLASH_ATTENTION", None)
    os.environ["DL4JTPU_FLASH_ATTENTION"] = "0"
    try:
        parts = [ref_fn(*(a[:, :, g:g + group].astype(jnp.float32)
                          for a in (q, k, v)))
                 for g in range(0, h, group)]
    finally:
        del os.environ["DL4JTPU_FLASH_ATTENTION"]
        if prior is not None:
            os.environ["DL4JTPU_FLASH_ATTENTION"] = prior
    ref_loss = sum(float(p[0]) for p in parts)
    ref = [jnp.concatenate([p[1][i] for p in parts], axis=2)
           for i in range(3)]
    fl_fn = grads(lambda q, k, v: flash_attention(q, k, v, True))
    # forward and ONE backward call: each tile's P and dS computed once
    n = kernel_calls(fl_fn.trace(q, k, v), f"{what}: flash forward+grad")
    check(n == 2, f"{what}: one forward and one backward kernel ({n})")
    fl_loss, fl = fl_fn(q, k, v)
    out = {"shape": list(shape)}
    for name, a, r in zip("qkv", fl, ref):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - r)))
        scale = float(jnp.max(jnp.abs(r)))
        out[f"d{name}_max_err"] = err
        # bf16 results: a few ulps at the largest magnitude
        check(math.isfinite(err) and err <= scale / 64.0,
              f"{what}: flash d{name} matches XLA (max err {err:.3g} vs "
              f"scale {scale:.3g})")
    rel = abs(float(fl_loss) - ref_loss) / max(1.0, abs(ref_loss))
    check(rel < 1e-2, f"{what}: flash forward loss matches XLA "
                      f"(rel {rel:.2g})")
    return out


def leg_kernel(cfg) -> dict:
    """Pallas flash fwd + bwd vs the XLA path, bf16: at the flagship head
    shape, and at the shape of the benchmark's training cell (one
    sequence of ``cell_t`` tokens, 32 heads of 64), where the backward is
    one call with the head's whole dq in VMEM."""
    h = cfg["n_heads"]
    return {"flagship": flash_parity(
                (2, cfg["kernel_t"], h, cfg["d_model"] // h), "flagship"),
            "cell": flash_parity(cfg["kernel_cell"], "cell")}


def build_net(cfg):
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    return ComputationGraph(transformer_lm(
        cfg["vocab"], n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], d_ff=cfg["d_ff"], learning_rate=3e-4,
        seed=SEED, dtype="mixed_bf16", input_ids=True,
        max_cache_t=cfg["page_size"] * cfg["pages_per_seq"])).init()


def token_batch(cfg, batch: int, t: int):
    import numpy as np
    ids = np.random.default_rng(SEED).integers(
        0, cfg["vocab"], (batch, t + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def step_clock():
    """A listener that keeps each step's loss (host value) and wall time."""
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class StepClock(TrainingListener):
        def __init__(self):
            self.losses, self.walls = [], []
            self._t = time.perf_counter()

        def iteration_done(self, model, iteration, score):
            self.losses.append(float(score))
            now = time.perf_counter()
            self.walls.append(round(now - self._t, 3))
            self._t = now

    return StepClock()


def kernel_calls(traced, what: str, also=None) -> int:
    """How many Pallas kernels the traced step holds: Mosaic custom calls
    in its lowering on the TPU (a silent XLA or interpreted route fails
    here), ``pallas_call`` in its jaxpr when --small asked for interpret
    mode. ``also``: a (lowered, jaxpr) pair of names that must be there
    too."""
    from deeplearning4j_tpu.util.xla import kernel_mode
    mosaic = kernel_mode() == "mosaic"
    text = traced.lower().as_text() if mosaic else str(traced.jaxpr)
    name = "tpu_custom_call" if mosaic else "pallas_call"
    n = text.count(name)
    extra = also[0 if mosaic else 1] if also else None
    check(n > 0 and (extra is None or extra in text),
          f"{what} holds {n} {name}" + (f" and {extra}" if extra else "")
          + ("" if mosaic else " (interpret mode, --small)"))
    return n


def check_losses(losses, per_token_start=None) -> None:
    """Finite, and falling on a repeated batch; from a random init the
    first loss (summed over time) is also about ``per_token_start`` a
    token."""
    check(all(math.isfinite(v) for v in losses),
          f"every loss finite: {[round(v, 4) for v in losses]}")
    if per_token_start is not None:
        tokens, nats = per_token_start
        check(abs(losses[0] / tokens - nats) < 1.0,
              f"first loss {losses[0] / tokens:.3f} a token is near "
              f"ln(V) = {nats:.3f} (random init)")
    check(losses[-1] < losses[0],
          f"last loss {losses[-1]:.4f} below first {losses[0]:.4f} on a "
          "repeated batch")


def leg_train(cfg, net, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import rng as _rng
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.util import metrics

    x, y = token_batch(cfg, cfg["train_batch"], cfg["train_t"])
    clock = step_clock()
    net.set_listeners(clock)
    c0 = compile_seconds(metrics.REGISTRY)
    try:
        net.fit(ListDataSetIterator([DataSet(x, y)] * cfg["train_steps"]))
    finally:
        net.set_listeners()
    out = {"batch": cfg["train_batch"], "seq_len": cfg["train_t"],
           "losses": [round(v, 4) for v in clock.losses],
           "step_wall_s": clock.walls,
           "compile_s": round(compile_seconds(metrics.REGISTRY) - c0, 2)}
    check(len(clock.losses) == cfg["train_steps"],
          f"fit() took {cfg['train_steps']} steps")
    check_losses(clock.losses, (cfg["train_t"], math.log(cfg["vocab"])))
    n = retraces(metrics.REGISTRY, "ComputationGraph.train_step")
    check(n == 1, f"jit_retraces_total{{train_step}} == 1 (got {n:g})")
    devs = {d.platform for leaf in jax.tree_util.tree_leaves(net.params)
            for d in leaf.devices()}
    check(devs == {platform}, f"parameters live on {platform} ({devs})")
    # what fit() dispatched, lowered again for these shapes: the kernel
    # must be IN it (a silent XLA or interpreted route fails here)
    step = net._train_step().__wrapped__
    traced = step.trace(
        net.params, net.updater_state, net._states_map(None),
        [jnp.asarray(x)], [jnp.asarray(y)], None,
        _rng.fold_name(_rng.key(net.training.seed), "update_0"),
        jnp.asarray(0, jnp.int32))
    n_calls = kernel_calls(traced, "train step")
    out["kernel_calls_in_step"] = n_calls
    return out


def _http(base, path, payload=None, timeout=120.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        base + path, data=data, method="GET" if data is None else "POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def leg_serve(cfg, net) -> dict:
    import numpy as np
    from deeplearning4j_tpu.models.transformer import oracle_stream_probs
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.util import flightrecorder, metrics

    t0 = time.perf_counter()
    server = InferenceServer(net, decode={
        "max_batch": cfg["lanes"], "page_size": cfg["page_size"],
        "pages_per_seq": cfg["pages_per_seq"],
        "block_len": cfg["block_len"]})
    out = {"warmup_wall_s": round(time.perf_counter() - t0, 2),
           "warmup_compile_s": round(compile_seconds(server.registry), 2)}
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _http(base, "/readyz")
        check(code == 200 and body.get("ready") is True,
              f"/readyz is 200 before the first request ({code} {body})")
        warm_traces = retraces(server.registry)
        out["programs_compiled"] = int(warm_traces)
        errors0 = len(flightrecorder.events("decode_error"))

        rng = np.random.default_rng(SEED)
        prompts = [rng.integers(0, cfg["vocab"], n).tolist()
                   for n in cfg["prompt_lens"]]
        n_new = cfg["new_tokens"]
        replies = [None] * len(prompts)

        def client(i):
            replies[i] = _http(base, "/generate", {
                "prompt_ids": prompts[i], "max_new_tokens": n_new})

        t1 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        check(not any(th.is_alive() for th in threads),
              "every concurrent client returned")
        # the same greedy prompt twice, on a quiet server: same programs,
        # same inputs, so the tokens must be identical
        again = [_http(base, "/generate", {"prompt_ids": prompts[0],
                                           "max_new_tokens": n_new})
                 for _ in range(2)]
        # and a long one again: its full pages are in the prefix cache now
        # (on by default), so this admission maps them instead of
        # prefilling; a different program, so equal tokens are reported,
        # not required
        hit = _http(base, "/generate", {"prompt_ids": prompts[3],
                                        "max_new_tokens": n_new})
        out["request_wall_s"] = round(time.perf_counter() - t1, 2)
        for i, (code, body) in enumerate(replies + again + [hit]):
            check(code == 200 and body.get("finish_reason") == "max_tokens"
                  and body.get("n_generated") == n_new
                  and all(0 <= tok < cfg["vocab"]
                          for tok in body.get("tokens", [])),
                  f"request {i}: 200, {n_new} tokens inside the vocabulary, "
                  f"finish_reason=max_tokens ({code} "
                  f"{ {k: v for k, v in body.items() if k != 'tokens'} })")
        check(again[0][1]["tokens"] == again[1][1]["tokens"],
              "the same greedy prompt sent twice gives the same tokens")
        hits = server.registry.get("kv_prefix_hits_total")
        n_hits = hits.value(result="partial") + hits.value(result="full")
        check(n_hits >= 1, f"the repeated long prompt hit the prefix cache "
                           f"({n_hits:g} hits)")
        out["prefix_hit_same_tokens"] = (
            hit[1]["tokens"] == replies[3][1]["tokens"])
        n = retraces(server.registry)
        check(n == warm_traces, "no compile after warm-up "
              f"(jit_retraces_total {warm_traces:g} -> {n:g})")
        n_err = len(flightrecorder.events("decode_error")) - errors0
        check(n_err == 0, f"no decode_error flight events ({n_err})")
        out["ttft_ms"] = [round(b.get("ttft_ms", -1.0), 1)
                          for _, b in replies]
    finally:
        server.stop(drain=False)

    # reference: the dense full-cache streaming path (rnn_time_step, the
    # oracle the paged engine is pinned against) must rate every served
    # token of request 0 at the top of its own distribution, within what
    # bf16 compute can move between two programs
    toks = again[0][1]["tokens"]
    c0 = compile_seconds(metrics.REGISTRY)
    probs = oracle_stream_probs(net, prompts[0] + toks[:-1])
    out["oracle_compile_s"] = round(compile_seconds(metrics.REGISTRY) - c0,
                                    2)
    ratios = [float(probs[len(prompts[0]) - 1 + i, tok]
                    / probs[len(prompts[0]) - 1 + i].max())
              for i, tok in enumerate(toks)]
    out["oracle_min_ratio"] = round(min(ratios), 4)
    check(min(ratios) > 0.9,
          "served tokens agree with the dense-cache oracle (lowest "
          f"p(token)/p(argmax) = {min(ratios):.4f})")
    return out


def leg_dp(cfg, net, devices) -> dict:
    """Same net, SyncTrainingMaster over a data mesh of every device."""
    import jax
    from deeplearning4j_tpu.parallel import (SyncTrainingMaster,
                                             data_parallel_mesh,
                                             host_local_batch)

    mesh = data_parallel_mesh()
    n = len(devices)
    trainer = SyncTrainingMaster().build(net, mesh)
    x, y = token_batch(cfg, n, cfg["train_t"])
    gx, gy = host_local_batch(mesh, x, y)
    check(gx.sharding.device_set == set(devices),
          f"the batch is laid over all {n} devices")
    clock = step_clock()
    net.set_listeners(clock)
    try:
        for _ in range(cfg["train_steps"]):
            trainer.fit_batch(gx, gy)
    finally:
        net.set_listeners()
        net._jit_cache.pop("train_step_override", None)
    check_losses(clock.losses)
    leaves = jax.tree_util.tree_leaves(net.params)
    check(all(leaf.sharding.device_set == set(devices) for leaf in leaves),
          f"every parameter is laid over all {n} devices")
    return {"batch": n, "seq_len": cfg["train_t"],
            "losses": [round(v, 4) for v in clock.losses],
            "step_wall_s": clock.walls,
            "bytes_in_use": device_bytes_in_use(devices)}


def leg_sp(cfg, net, devices) -> dict:
    """Same net, time axis sharded over a 4-device ring; per-device shards
    are long enough that every hop runs the Pallas kernel."""
    import jax.numpy as jnp
    from deeplearning4j_tpu import rng as _rng
    from deeplearning4j_tpu.ops.attention import ring_flash_available
    from deeplearning4j_tpu.parallel import (SequenceParallelGraphTrainer,
                                             create_mesh)
    from deeplearning4j_tpu.util import metrics

    mesh = create_mesh({"seq": 4}, devices=devices[:4])
    t = cfg["sp_t"]
    check(ring_flash_available(t // 4),
          f"ring attention takes the Pallas hop route at t_local={t // 4}")
    trainer = SequenceParallelGraphTrainer(net, mesh)
    x, y = token_batch(cfg, 1, t)
    c0 = compile_seconds(metrics.REGISTRY)
    clock = step_clock()
    net.set_listeners(clock)
    try:
        for _ in range(cfg["train_steps"]):
            trainer.fit_batch(x, y)
    finally:
        net.set_listeners()
    check_losses(clock.losses)
    step = next(iter(trainer._step_fns.values())).__wrapped__
    traced = step.trace(
        net.params, net.updater_state, net._states_map(None),
        [trainer._stage(x)], [trainer._stage(y)], None,
        _rng.fold_name(_rng.key(net.training.seed), "update_0"),
        jnp.asarray(0, jnp.int32))
    n_calls = kernel_calls(traced, "ring step",
                           also=("collective_permute", "ppermute"))
    return {"batch": 1, "seq_len": t, "t_local": t // 4,
            "losses": [round(v, 4) for v in clock.losses],
            "step_wall_s": clock.walls,
            "compile_s": round(compile_seconds(metrics.REGISTRY) - c0, 2),
            "kernel_calls_in_step": n_calls,
            "bytes_in_use": device_bytes_in_use(devices[:4])}


def device_bytes_in_use(devices):
    """``memory_stats()["bytes_in_use"]`` per device; every one must hold
    something (backends without memory stats report None and skip)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    used = [int(s["bytes_in_use"]) for s in stats]
    check(all(b > 0 for b in used),
          f"every device holds parameters or activations ({used})")
    return used


# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--small", action="store_true",
                    help="toy sizes on any backend, kernels interpreted: "
                         "debugs this script, proves nothing about the chip")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"jax {jax.__version__}  platform={device['platform']}  "
          f"device_kind={device['kind']!r}  devices={device['count']}",
          flush=True)
    if device["platform"] != "tpu" and not args.small:
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' "
              "- no accelerator, nothing was run (--small runs the toy "
              "size for debugging)", file=sys.stderr)
        return 2

    import contextlib
    from deeplearning4j_tpu.util.xla import interpret_kernels, use_compile_cache
    cache = {"dir": use_compile_cache(), "requests": 0, "hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1

    jax.monitoring.register_event_listener(on_event)

    cfg = SMALL if args.small else FULL
    if args.small:
        # toy lengths sit below the auto-route thresholds: force the
        # kernel route, and ask for interpret mode where there is no TPU
        os.environ["DL4JTPU_FLASH_ATTENTION"] = "1"
        mode = (interpret_kernels() if device["platform"] != "tpu"
                else contextlib.nullcontext())
    else:
        if "DL4JTPU_FLASH_ATTENTION" in os.environ:
            print("chip_smoke: unset DL4JTPU_FLASH_ATTENTION - the smoke "
                  "proves the route the code picks by itself",
                  file=sys.stderr)
            return 2
        mode = contextlib.nullcontext()

    legs, failed = {}, []
    multi = len(devices) >= 4
    if not multi:
        print(f"{len(devices)} device(s): the four-chip legs (dp4, sp4) "
              "are left out", flush=True)

    def run(name, fn, *a):
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            legs[name] = fn(*a)
        except Exception:   # noqa: BLE001 - every failure is reported below
            traceback.print_exc()
            legs[name] = {"error": traceback.format_exc(limit=1)
                          .strip().splitlines()[-1]}
            failed.append(name)
        legs[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        print(f"   {name}: {json.dumps(legs[name])}", flush=True)

    with mode:
        run("kernel", leg_kernel, cfg)
        net = build_net(cfg)
        run("train", leg_train, cfg, net, device["platform"])
        run("serve", leg_serve, cfg, net)
        if multi:
            run("dp4", leg_dp, cfg, net, devices)
            run("sp4", leg_sp, cfg, net, devices)

    stats = devices[0].memory_stats()
    peak = int(stats["peak_bytes_in_use"]) if stats else None
    print(f"peak HBM (device 0): "
          f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}; "
          f"compile cache {cache['dir']}: {cache['hits']} hits of "
          f"{cache['requests']} compile requests", flush=True)
    print("summary: " + json.dumps(
        {"jax": jax.__version__, "small": args.small, "legs": legs,
         "failed": failed, "peak_hbm_bytes": peak, "compile_cache": cache,
         "claim": None}), flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
    # the result line: these two keys and nothing else, last on stdout
    print(json.dumps({"ok": not failed, "device": device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
