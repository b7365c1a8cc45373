"""Loop cells: the whole FL loop, ``FLSimulation.run``, round by round.

Traffic parameters (``traffic/<name>.json``):

    fl             the builder's FL settings (see ``models.loop_parts``):
                   FLConfig fields for ``image``, ``build_lm_fl`` keyword
                   arguments for ``lm``
    sim            SimConfig fields (speed_model, pareto_shape, ...)
    data           for the ``image`` builder: n_train, n_test,
                   dirichlet_alpha
    fleet_seed     seed of the data set, its partition, the clients'
                   timing and the server's selection, fixed for every
                   run: every seed trains the same clients, shard sizes
                   and arrivals (and the same compiled epoch programs),
                   so the seed does not change the work; the run seed
                   draws the weights and every client's batch order
    eval_every     aggregations between evaluations
    check_rounds   the window round whose aggregation is compared is
                   drawn from the seed among the window's first
                   ``check_rounds``
    trace_seconds  length of the traced window with ``--trace 1``

Set-up builds the program's own FL set-up for the configuration's builder,
gives its server the benchmark's weights, compiles every client epoch
program the shards need, and runs the first round through the window's own
call.  The window then runs one round per call until ``--seconds`` have
passed.  What belongs to one model, its reference SGD step and its forward
FLOPs, comes from the configuration's reference ``configs/<name>.ref.py``.

``correct`` compares each stage of the loop with the plain reference run
on that stage's own inputs, so that no error builds up over epochs,
clients and rounds:

    step_gap         the run's first epoch call (the first client's first
                     epoch, from the seed's weights): its change by the
                     worst leaf; ``step_median_gap`` by the median leaf
    loss_gap         that call's mean loss
    agg_gap          round 1's and a window round's new global, by Eq.
                     (4)-(8) over the buffer rows the program aggregated
                     (the window round's clients are stale): the widest
                     gap over the reference's largest magnitude
    weights_gap      those two rounds' Eq. (6) weights: the widest gap
    dispatch_gap     the model the first client trained from after that
                     window round, against the reference's new global
    window_step_gap  that client's first epoch call, against the reference
                     trained from its own new global: by the worst leaf;
                     ``window_median_gap`` by the median leaf
    ingest_gap       round 1's buffer rows against the packed models the
                     clients trained
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip import costs, models, spec, weights

HYPER_KEYS = ("alpha", "mu", "beta", "theta")


def batches(data) -> int:
    """Samples in an epoch's batches: (n_batches, batch, ...) leaves."""
    import jax

    return int(np.prod(jax.tree.leaves(data)[0].shape[:2]))


class State:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from jax.profiler import TraceAnnotation

        from repro.core.server import SeaflServer
        from repro.runtime.simulator import FLSimulation, SimConfig

        self.config, self.traffic, self.seed = config, traffic, seed
        self.ref = spec.reference(config["name"])
        self.rule = getattr(self.ref, "init_rule", weights.fan_in)
        clients, eval_fn, fl = models.loop_parts(config, traffic)
        self.fl = fl
        self.hyper = {k: float(getattr(fl.hyper(), k)) for k in HYPER_KEYS}
        self.shapes = models.param_shapes(config)
        params = weights.init(self.shapes, seed, self.rule)
        server = SeaflServer(fl, params, {c: cl.n_samples
                                          for c, cl in clients.items()})
        del params
        simcfg = SimConfig(**traffic["sim"], seed=traffic["fleet_seed"])
        self.sim = FLSimulation(server, clients, simcfg,
                                eval_fn=self._wrap_eval(eval_fn,
                                                        TraceAnnotation),
                                eval_every=traffic["eval_every"])
        self.server = server
        self.samples = self.updates = 0
        self.recording = True          # round 1, in set-up
        self.finals: dict[int, object] = {}
        self.snaps: list[dict] = []
        self.agg_rounds = {0}
        self.first_step = self.window_step = None
        self.window_version = None
        self.draw = int(np.random.default_rng([seed, 7]).integers(
            traffic["check_rounds"]))
        epoch = next(iter(clients.values())).epoch_fn
        self.epoch = epoch
        self._cur: dict = {}
        for cid, cl in clients.items():
            cl._rng = np.random.default_rng([seed, cid])
            cl.epoch_fn = self._wrap_epoch(epoch, TraceAnnotation)
            cl.local_train = self._wrap_train(cid, cl.local_train)
        self._wrap_server(server, TraceAnnotation)
        self.sizes = {c: cl.n_samples for c, cl in clients.items()}
        self.batch = next(iter(clients.values())).batch_size

    def _wrap_train(self, cid, train):
        import jax

        def call(params, n_epochs, lr):
            self._cur = {"cid": cid, "version": self.server.active[cid],
                         "epoch": 0}
            out = train(params, n_epochs, lr)
            if self.recording:
                self.finals[cid] = jax.device_get(out[0])
            return out
        return call

    def _wrap_epoch(self, epoch, TraceAnnotation):
        import jax

        def call(params, data, lr):
            with TraceAnnotation("bench.client_epoch"):
                out = epoch(params, data, lr)
            self.samples += batches(data)
            cur = self._cur
            if cur["epoch"] == 0 and (
                    self.first_step is None
                    or (self.window_step is None
                        and cur["version"] == self.window_version)):
                rec = jax.device_get({"data": data, "lr": lr, "base": params,
                                      "out": out[0], "loss": out[1]})
                if self.first_step is None:
                    self.first_step = rec
                else:
                    self.window_step = rec
            cur["epoch"] += 1
            return out
        return call

    def _wrap_server(self, server, TraceAnnotation):
        ingest, aggregate = server.ingest_payload, server._aggregate

        def ingest_payload(payload, recv_time=0.0):
            self.updates += 1
            with TraceAnnotation("bench.ingest"):
                return ingest(payload, recv_time)

        def agg(now):
            r = server.round
            snap = None
            if r in self.agg_rounds:
                ups = server.buffer.updates()
                snap = {"round": r, "pre": np.asarray(server.global_flat),
                        "rows": np.asarray(server.buffer.stacked_flat()),
                        "sizes": np.asarray([u.n_samples for u in ups],
                                            np.float32),
                        "stale": np.asarray([r - u.version for u in ups],
                                            np.float32),
                        "cids": [u.client_id for u in ups]}
            with TraceAnnotation("bench.aggregate"):
                ev = aggregate(now)
            if snap is not None:
                snap.update(new=np.asarray(server.global_flat),
                            weights=np.asarray(ev.weights, np.float32))
                self.snaps.append(snap)
                if r != 0:
                    self.window_version = server.round
            return ev
        server.ingest_payload, server._aggregate = ingest_payload, agg

    @staticmethod
    def _wrap_eval(fn, TraceAnnotation):
        def call(params):
            with TraceAnnotation("bench.eval"):
                return fn(params)
        return call

    def warm_epochs(self) -> None:
        """Compile (or load) the epoch program of every shard length,
        outside the simulation, on the benchmark's weights."""
        import jax

        params = self.server.params
        data = next(iter(self.sim.clients.values())).data
        for nb in sorted({max(1, n // self.batch)
                          for n in self.sizes.values()}):
            zeros = jax.tree.map(lambda a: np.zeros(
                (nb, self.batch) + a.shape[1:], a.dtype), data)
            jax.block_until_ready(self.epoch(params, zeros,
                                             self.fl.local_lr))


def _round(st: State) -> None:
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.round"):
        st.sim.run(max_rounds=st.server.round + 1)


def setup(config: dict, traffic: dict, seed: int) -> State:
    t0 = time.perf_counter()
    st = State(config, traffic, seed)
    t1 = time.perf_counter()
    st.warm_epochs()
    t2 = time.perf_counter()
    _round(st)
    st.recording = False
    cids = set(st.snaps[0]["cids"])
    st.finals = {c: m for c, m in st.finals.items() if c in cids}
    print(f"[chipbench] setup phases: build {t1 - t0:.3f} s, epoch programs "
          f"{t2 - t1:.3f} s, first round {time.perf_counter() - t2:.3f} s",
          file=sys.stderr)
    return st


MAX_EXTRA_ROUNDS = 50


def window(st: State, seconds: float) -> dict:
    """Rounds until ``seconds`` have passed and the window round drawn for
    the check has been aggregated and trained from; the window closes at
    the end of the round in which both hold."""
    s0, u0, r0 = st.samples, st.updates, st.server.round
    st.agg_rounds = {r0 + st.draw}
    t0 = time.perf_counter()
    while True:
        _round(st)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds and st.window_step is not None:
            break
        if st.server.round - r0 > st.draw + MAX_EXTRA_ROUNDS:
            raise RuntimeError("no client trained from the window round "
                               f"{r0 + st.draw} drawn for the check")
    return {
        "attempted": st.updates - u0, "failed": 0,
        "resident": st.server.resident_state_bytes(),
        "window_s": elapsed, "samples": st.samples - s0,
        "rounds": st.server.round - r0,
        "train_flops_per_sample": costs.train_flops(
            st.ref.forward_flops(st.config)),
    }


def release(st: State) -> None:
    """Free the program's state; what the check needs was copied to the
    host as it was made, so that it holds no device memory."""
    st.sim = st.server = None


def _epoch(st: State, dtype, fault: str):
    """The reference's epoch call: one SGD step per batch, in order; the
    mean of the steps' losses.  ``fault='half_batch'`` trains on the first
    half of every batch (a fault read for the limits, never in a run)."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda p, b, lr: st.ref.sgd_step(p, b, lr, st.config,
                                                    dtype))

    def run(p, data, lr):
        losses = []
        for i in range(jax.tree.leaves(data)[0].shape[0]):
            batch = jax.tree.map(lambda a: a[i], data)
            if fault == "half_batch":
                batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
            p, lv = step(p, batch, lr)
            losses.append(lv)
        return p, float(jnp.mean(jnp.stack(losses).astype(jnp.float32)))
    return run


def _nudge(tree, fault: str):
    """``fault='nudge'``: every weight one or two ulps up, the reference's
    own rounding-level start, to read how far rounding alone carries an
    epoch call (a reading for the limits, never in a run)."""
    import jax

    if fault != "nudge":
        return tree
    return jax.tree.map(lambda x: x * (1 + 2 ** -22), tree)


def reference(st: State, dtype, fault: str = "") -> dict:
    """Each compared stage by the plain reference, on that stage's inputs:
    the first epoch call from the seed's weights, the two snapshot rounds'
    Eq. (4)-(8) over the rows the program aggregated, and the window
    client's first epoch call from the reference's own new global."""
    import jax.numpy as jnp

    from benchmarks.chip.reference import aggregation as agg_ref

    epoch = _epoch(st, dtype, fault)
    p0 = _nudge(weights.init(st.shapes, st.seed, st.rule), fault)
    s = st.first_step
    aggs = [agg_ref.aggregate(jnp.asarray(r["pre"]), jnp.asarray(r["rows"]),
                              jnp.asarray(r["sizes"]),
                              jnp.asarray(r["stale"]), st.hyper, dtype)
            for r in st.snaps]
    w_base = _nudge(weights.unflatten(aggs[-1][0], st.shapes), fault)
    w = st.window_step
    return {"base": p0, "step": epoch(p0, s["data"], s["lr"]),
            "aggs": aggs, "w_flat": aggs[-1][0], "w_base": w_base,
            "w_step": epoch(w_base, w["data"], w["lr"]), "ingest_gap": 0.0}


def program(st: State) -> dict:
    import jax.numpy as jnp

    rows, ingest = st.snaps[0]["rows"], 0.0
    for i, c in enumerate(st.snaps[0]["cids"]):
        ingest = max(ingest, float(jnp.max(jnp.abs(
            jnp.asarray(rows[i]) - weights.flatten(st.finals[c])))))
    f, w = st.first_step, st.window_step
    return {"step": (f["out"], float(f["loss"])),
            "aggs": [(r["new"], r["weights"]) for r in st.snaps],
            "w_flat": weights.flatten(w["base"]),
            "w_step": (w["out"], float(w["loss"])), "ingest_gap": ingest}


def _norms(a, base) -> list[float]:
    import jax
    import jax.numpy as jnp

    return [float(jnp.linalg.norm((jnp.asarray(x, jnp.float32)
                                   - jnp.asarray(b, jnp.float32)).ravel()))
            for x, b in zip(jax.tree.leaves(a), jax.tree.leaves(base))]


def leaf_gaps(got: list[float], want: list[float], keep,
              med: float) -> list[float]:
    """Each kept leaf's | |got| - |want| |, over the larger of |want| and
    the median leaf's |want| ``med``."""
    return [abs(a - b) / max(b, med) for a, b, k in zip(got, want, keep)
            if k]


def _change_gaps(got, want, base, detail, info, name):
    """The worst and the median kept leaf's gap between the two sides'
    changes from the reference's starting point ``base`` (so a program
    that started from another model reads it too).  Leaves whose change in
    the reference is under a thousandth of the median moving leaf's are
    left out (no gradient but round-off reaches them)."""
    gn, wn = _norms(got, base), _norms(want, base)
    moved = [r for r in wn if r > 0]
    med = float(np.median(moved)) if moved else 0.0
    keep = [r > 0 and r >= 1e-3 * med for r in wn]
    rel = leaf_gaps(gn, wn, keep, med)
    if detail:
        info[name] = _worst(rel, keep, want)
    if not rel:
        return float("inf"), float("inf")
    return _num(np.max(rel)), _num(_median(rel))


def _median(values: list[float]) -> float:
    """The upper of the two middle values where their count is even."""
    return sorted(values)[len(values) // 2]


def _worst(rel, keep, tree) -> dict:
    import jax

    paths = [jax.tree_util.keystr(kp)
             for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    kept = [p for p, k in zip(paths, keep) if k]
    return {"median_leaf_gap": float(np.median(rel)),
            "left_out": len(paths) - len(kept),
            "worst5": sorted(((round(r, 6), p) for r, p in zip(rel, kept)),
                             reverse=True)[:5]}


def _num(x) -> float:
    """A gap as compared: one that is not a number (a reference or program
    gone to NaN, nothing left to compare) reads infinite."""
    x = float(x)
    return x if x == x else float("inf")


def _wide(a, b) -> float:
    import jax.numpy as jnp

    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    if a.shape != b.shape:
        return float("inf")
    return _num(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def gaps(got: dict, want: dict, detail: bool = False) -> dict:
    """The numbers of the module docstring, ``got`` against ``want``."""
    info: dict = {}
    step = _change_gaps(got["step"][0], want["step"][0], want["base"],
                        detail, info, "step_gap")
    w_step = _change_gaps(got["w_step"][0], want["w_step"][0],
                          want["w_base"], detail, info, "window_step_gap")
    out = {
        "step_gap": step[0], "step_median_gap": step[1],
        "loss_gap": _num(abs(got["step"][1] - want["step"][1])
                         / abs(want["step"][1])),
        "agg_gap": max(_wide(g[0], w[0])
                       for g, w in zip(got["aggs"], want["aggs"])),
        "weights_gap": max(
            _num(np.max(np.abs(np.asarray(g[1]) - np.asarray(w[1]))))
            if np.shape(g[1]) == np.shape(w[1]) else float("inf")
            for g, w in zip(got["aggs"], want["aggs"])),
        "dispatch_gap": _wide(got["w_flat"], want["w_flat"]),
        "window_step_gap": w_step[0], "window_median_gap": w_step[1],
        "ingest_gap": got["ingest_gap"],
    }
    if detail:
        info["losses"] = [got["step"][1], want["step"][1]]
        out["detail"] = info
    return out


def check(st: State, detail: bool = False) -> dict:
    """The numbers ``correct`` compares; with ``detail`` also the worst
    leaves, for setting and explaining the limits."""
    import jax.numpy as jnp

    out = gaps(program(st), reference(st, jnp.float32), detail)
    if detail:
        out["detail"]["window_stale"] = st.snaps[-1]["stale"].tolist()
    return out


def control(st: State, detail: bool = False, fault: str = "") -> dict:
    """The reference in bfloat16 put in the program's place; with
    ``fault``, the float32 reference with that fault planted instead."""
    import jax.numpy as jnp

    want = reference(st, jnp.float32)
    got = (reference(st, jnp.float32, fault) if fault
           else reference(st, jnp.bfloat16))
    return gaps(got, want, detail)
