"""Runner of the training cells: ``ComputationGraph.fit(iterator)``.

Set-up builds ONE net with its compiled step and optimizer state, installs
the weights made from the seed, and drives it through its first
``check_steps`` steps through the very call and feed the window uses
(``net.fit`` over a generator of batches); a listener reads what the
comparison needs from them. The same net then goes into the window: the
device is drained, the clock starts, the generator hands ``fit`` batches
until ``--seconds`` have passed and ends, ``fit`` returns, the device is
drained, the clock stops. Every step inside is whole and all time inside is
a step's. Then the net is freed and the plain reference follows the first
steps from the same seed.
"""

from __future__ import annotations

import time

import numpy as np

from . import common, compare, control, traffic, weights


def build_net(family, cfg: dict, seed: int, max_cache_t=None):
    """The program's net for ``cfg``, as its family configures it, with the
    benchmark's weights in it."""
    import gc
    import jax
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    net = ComputationGraph(family.build_conf(cfg, seed, max_cache_t)).init()
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), net.params)
    net.params = None               # the program's own draw is not used
    gc.collect()
    net.params = weights.to_program(
        family, cfg, weights.make_weights(family, cfg, seed), like)
    return net


class Probe:
    """Listener of the first steps: each step's loss, the first gradient's
    norm by leaf (from Adam's first moment after one step: m1 = (1 - b1)
    g1), and the norm by leaf of the parameters' change after the last."""

    def __init__(self, family, cfg: dict, seed: int, steps: int):
        self.family, self.cfg, self.seed, self.steps = family, cfg, seed, steps
        self.losses, self.grad_norms, self.change_norms = [], None, None
        self.first_moment = None

    def iteration_done(self, model, iteration, score):
        self.losses.append(float(score))
        if iteration == 1:
            m = weights.from_program(self.family, self.cfg,
                                     model.updater_state["m"])
            self.grad_norms = weights.leaf_norms(m)
            # the gradient itself waits on the host for the reference's
            self.first_moment = {k: np.asarray(v) for k, v in m.items()}
        if iteration == self.steps:
            self.change_norms = weights.change_norms(
                self.family, self.cfg, self.seed,
                weights.from_program(self.family, self.cfg, model.params))

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass

    def result(self, beta1: float = 0.9) -> dict:
        return {"losses": self.losses,
                "first_grads": {k: v / np.float32(1.0 - beta1)
                                for k, v in self.first_moment.items()},
                "grad_norms": {k: float(v) / (1.0 - beta1)
                               for k, v in self.grad_norms.items()},
                "change_norms": {k: float(v)
                                 for k, v in self.change_norms.items()}}


class TimedFeed:
    """The window's iterator: batches from ``first_step`` on, until
    ``seconds`` have passed since ``start()``; then it ends."""

    def __init__(self, mix, vocab, seed, first_step, seconds, chips=1):
        self.mix, self.vocab, self.seed, self.chips = mix, vocab, seed, chips
        self.step, self.seconds, self.count = first_step, seconds, 0
        self.t0 = None

    def start(self):
        self.t0 = time.perf_counter()

    def __iter__(self):
        while time.perf_counter() - self.t0 < self.seconds:
            yield traffic.train_batch(self.mix, self.vocab, self.seed,
                                      self.step, self.chips)
            self.step += 1
            self.count += 1


def drain(net) -> None:
    import jax
    jax.block_until_ready((net.params, net.updater_state))


def run(cell: dict, cfg: dict, mix: dict, args, env: dict) -> dict:
    from deeplearning4j_tpu.util import metrics

    family, chips = env["family"], cell["chips"]
    if chips != 1:
        raise SystemExit("benchmark: the data-parallel training cell is not "
                         "built yet (PERF.md, Open questions)")
    check_steps = int(mix.get("check_steps", 3))
    parts = {"imports": common.process_age_s()}
    net = build_net(family, cfg, args.seed)
    parts["build_net"] = common.process_age_s()
    if env.get("plant") is not None:          # tests plant faults here
        env["plant"](net)
    probe = Probe(family, cfg, args.seed, check_steps)
    net.set_listeners(probe)
    net.fit(traffic.train_batch(mix, cfg["vocab_size"], args.seed, s, chips)
            for s in range(check_steps))
    net.set_listeners()
    program = probe.result()
    parts["first_steps"] = common.process_age_s()

    registries = [metrics.REGISTRY]
    edges = common.Edges(registries, env["wants"])
    feed = TimedFeed(mix, cfg["vocab_size"], args.seed, check_steps,
                     args.seconds, chips)
    tracer = None
    if env["tracing"]:
        tracer = common.TraceSlice(env["trace_dir"],
                                   *common.trace_plan(args.seconds))
    drain(net)
    compiles0 = env["compiles"].count
    edges.open()
    setup_s = common.process_age_s()
    t0 = time.perf_counter()
    feed.start()
    if tracer:
        tracer.start()
    net.fit(iter(feed))
    drain(net)
    window_s = time.perf_counter() - t0
    edges.close()
    compiles = env["compiles"].count - compiles0
    if tracer:
        tracer.join()
        if tracer.error:
            raise tracer.error
    steps = feed.count
    tokens = steps * mix["batch"] * mix["seq_len"] * chips
    peak = common.memory_peak_bytes()

    out = {"attempted": steps, "failed": 0, "window_s": window_s,
           "steps": steps, "tokens": tokens, "setup_s": setup_s,
           "flops": tokens * family.train_flops_per_token(
               cfg, mix["seq_len"]),
           "memory_peak_bytes": peak, "compiles_in_window": compiles,
           "edges": edges, "chips": chips,
           "end_to_end": {
               "setup_s": setup_s,
               "train_tokens_per_s_per_chip": tokens / window_s / chips}}

    # free the program's state, then let the reference follow the steps
    del net, probe, feed
    common.free_device_memory()
    t_ref = time.perf_counter()
    reference = control.reference_training(
        common.load_reference(cfg), family, cfg, mix, args.seed, chips,
        first_grads=program.pop("first_grads"))
    out["reference_s"] = time.perf_counter() - t_ref
    verdict = compare.Verdict()
    verdict.require("steps_in_window", steps >= 1, f"{steps} steps")
    verdict.require("no_compile_in_window", compiles == 0, f"{compiles}")
    compare.compare_training(verdict, cfg["limits"], program, reference)
    out["verdict"] = verdict
    out["readings"] = {"program": program, "reference": reference,
                       "setup_parts_s": parts}
    return out
