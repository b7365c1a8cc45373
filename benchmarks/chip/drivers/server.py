"""Server cells: a closed loop of client uploads into ``SeaflServer``.

Traffic parameters (``traffic/<name>.json``):

    fl              FLConfig fields (algorithm, n_clients, concurrency,
                    buffer_size, compression, dispatch_compression, ...)
    client_sizes    the data sizes n_k, one per client; the seed permutes
                    which client holds which
    pool            client models made in set-up: the global plus a small
                    seeded step each, encoded once by the client-side
                    ``encode_update`` and replayed
    global_scale    std of the seeded global, step_scale of the step
    max_staleness   the next uploader is drawn from the seed among the
                    in-flight clients, from the stalest alone once one
                    has reached this staleness
    warmup_rounds   rounds run in set-up, through the same loop
    trace_seconds   length of the traced window with ``--trace 1``
    sampled_globals window rounds whose new global is compared with the
                    reference (drawn from the seed), besides the last

Each global kept for the check is copied to host memory when it is taken,
so the window's device memory holds no copy made for the check
(``State.keep``).  On several chips (``run.py`` installs a ``pod`` mesh),
the seeded tree, the flat global and every pool row are made split over
the mesh (``weights.sharding``).

The window drives ``ingest_payload`` (which aggregates every K uploads)
and, after each aggregation, ``encode_dispatch`` and ``deliver_dispatch``
for every client the server sends back to training, as ``FLSimulation``
calls them.  A round runs from the ingest call of the upload that triggers
it until the new global and the round's dispatch payloads are ready.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.chip import costs, models, weights
from benchmarks.chip.reference import aggregation as ref

HYPER_KEYS = ("alpha", "mu", "beta", "theta")


class State:
    """One server cell as set-up leaves it for the window."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax

        from repro.core.server import FLConfig, SeaflServer
        from repro.runtime.transport import encode_update

        self.traffic, self.seed = traffic, seed
        self.rng = np.random.default_rng(seed)
        shapes = models.param_shapes(config)
        rule = weights.normal_all(traffic["global_scale"])
        self.g0_fn = lambda: weights.flatten(weights.init(shapes, seed, rule))
        params = weights.init(shapes, seed, rule)
        g0 = weights.flatten(params)
        fl = FLConfig(**traffic["fl"], seed=seed)
        self.fl = fl
        self.hyper = {k: float(getattr(fl.hyper(), k)) for k in HYPER_KEYS}
        sizes = list(traffic["client_sizes"])
        if len(sizes) != fl.n_clients:
            raise ValueError("client_sizes must give one size per client")
        self.sizes = dict(enumerate(
            int(s) for s in self.rng.permutation(sizes)))
        self.server = SeaflServer(fl, params, self.sizes)
        del params
        self.p = self.server.packer.size
        self.pool = []
        for i in range(traffic["pool"]):
            row = self.pool_row(g0, i)
            self.pool.append(encode_update(i, 0, fl.local_epochs, row,
                                           self.server.wire))
            del row
        del g0
        jax.block_until_ready([c.payload for pl in self.pool
                               for c in pl.chunks])
        self.held = {c: 0 for c in self.server.start()}
        self.rounds: list[dict] = []
        self.uploads: list[tuple] = []
        self.kept_globals: dict[int, object] = {}
        self.copier = ThreadPoolExecutor(max_workers=1)
        self.sampled: list[int] = []
        self.sample_rng = np.random.default_rng([seed, 1])

    def pool_row(self, g0, i: int):
        """Client model ``i`` of the pool: the global plus a seeded step."""
        import jax

        k = jax.random.fold_in(weights.key(self.seed), 1_000_003 + i)
        return _step(g0, k, self.traffic["step_scale"])

    def keep(self) -> None:
        """Keep the server's current global for the check, in host memory:
        the copy starts at once and a worker thread waits for it, so the
        loop never does.  It lands in ``kept_globals`` as a numpy array at
        ``release``; the device buffer goes once the copy is done and the
        server has moved on."""
        g = self.server.global_flat
        g.copy_to_host_async()
        self.kept_globals[len(self.rounds)] = self.copier.submit(np.asarray,
                                                                 g)

    def next_uploader(self) -> int:
        inflight = sorted(self.held)
        t = len(self.rounds)
        stale = max(t - self.held[c] for c in inflight)
        if stale >= self.traffic["max_staleness"]:
            inflight = [c for c in inflight if t - self.held[c] == stale]
        return int(inflight[self.rng.integers(len(inflight))])


_STEP = {}


def _step(g0, k, scale):
    return step_program(weights.sharding(g0.shape, 0))(g0, k, scale)


def step_program(where):
    """The jitted ``(g0, key, scale) -> row`` of a pool row, its output in
    ``where`` (None: where JAX puts it)."""
    import jax

    if where not in _STEP:
        _STEP[where] = weights.placed(
            lambda g, key, s: g + s * jax.random.normal(key, g.shape,
                                                        g.dtype), where)
    return _STEP[where]


def one_round(st: State, spans: dict) -> None:
    """Uploads until one aggregation, then its dispatch; records the
    round for the reference."""
    import jax
    from jax.profiler import TraceAnnotation

    server = st.server
    while True:
        cid = st.next_uploader()
        pi = int(st.rng.integers(len(st.pool)))
        version = st.held.pop(cid)
        payload = dataclasses.replace(st.pool[pi], cid=cid, version=version)
        st.uploads.append((pi, cid, version))
        t0 = time.perf_counter()
        with TraceAnnotation("bench.ingest"):
            ev = server.ingest_payload(payload)
        t1 = time.perf_counter()
        if ev is None:
            spans["ingest"].append(t1 - t0)
            continue
        with TraceAnnotation("bench.dispatch"):
            sent = [server.encode_dispatch(c, materialize=False)
                    for c in ev.dispatch]
            for c, pl in zip(ev.dispatch, sent):
                server.deliver_dispatch(c, pl)
        t2 = time.perf_counter()
        with TraceAnnotation("bench.round_wait"):
            jax.block_until_ready((server.global_flat,
                                   [pl.chunks for pl in sent if pl.chunks]))
        t3 = time.perf_counter()
        spans["dispatch"].append(t2 - t1)
        spans["round"].append(t3 - t0)
        k = len(ev.contributors)
        st.rounds.append({"uploads": st.uploads[-k:],
                          "weights": np.asarray(ev.weights, np.float32)})
        for c in ev.dispatch:
            if c in st.held:
                raise RuntimeError(f"client {c} dispatched while in flight")
            st.held[c] = len(st.rounds)
        return


def setup(config: dict, traffic: dict, seed: int) -> State:
    t0 = time.perf_counter()
    st = State(config, traffic, seed)
    t1 = time.perf_counter()
    spans = {"ingest": [], "dispatch": [], "round": []}
    for _ in range(traffic["warmup_rounds"]):
        one_round(st, spans)
    print(f"[chipbench] setup phases: server and pool {t1 - t0:.3f} s, "
          f"warm-up rounds {time.perf_counter() - t1:.3f} s", file=sys.stderr)
    st.keep()
    return st


def sample(st: State, n: int) -> None:
    """Keep the new global of the ``n``-th window round in a reservoir of
    ``sampled_globals`` rounds drawn from the seed."""
    keep, now = st.traffic["sampled_globals"], len(st.rounds)
    if n <= keep:
        st.sampled.append(now)
    else:
        j = int(st.sample_rng.integers(n))
        if j >= keep:
            return
        st.kept_globals.pop(st.sampled[j])
        st.sampled[j] = now
    st.keep()


def window(st: State, seconds: float) -> dict:
    """Rounds until ``seconds`` have passed; the window closes at the end
    of the round in which they pass."""
    spans = {"ingest": [], "dispatch": [], "round": []}
    first = len(st.rounds)
    t0 = time.perf_counter()
    while True:
        one_round(st, spans)
        sample(st, len(st.rounds) - first)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    st.keep()
    fl = st.fl
    buf_item = 2 if fl.buffer_dtype == "bfloat16" else 4
    wire = st.server.wire
    wire_item = st.pool[0].nbytes / st.p
    rounds = len(st.rounds) - first
    absorbed = sum(len(r["uploads"]) for r in st.rounds[first:])
    k = fl.buffer_size
    return {
        "attempted": absorbed, "failed": 0,
        "resident": st.server.resident_state_bytes(),
        "window_s": elapsed, "rounds": rounds, "absorbed": absorbed,
        "spans": spans, "p": st.p, "k": k, "wire_scheme": wire.scheme,
        "agg_least_bytes": costs.agg_least_bytes(k, st.p, buf_item),
        "agg_flops": costs.agg_flops(k, st.p),
        "ingest_least_bytes": costs.ingest_least_bytes(st.p, wire_item,
                                                       buf_item),
    }


def release(st: State) -> None:
    """Free the program's state; the sampled globals, copied to host
    memory, and the records stay for the check."""
    st.server = None
    st.pool = None
    st.copier.shutdown(wait=True)
    st.kept_globals = {r: c.result() for r, c in st.kept_globals.items()}


def reference(st: State, dtype, against: dict | None = None) -> dict:
    """Every round replayed by the plain reference from the seed's global
    and pool, in P-blocks and split as the cell's arrays are.  Returns the
    weights of each round and, for each round whose program global was
    kept, either the widest gaps ``(d, a, b)`` of the reference's new global
    to the kept copy in ``against`` (max |a - b|, max |against|, max |new|),
    compared on the device block by block, or, with ``against`` None, a
    host copy of the new global."""
    import jax

    from repro.sharding import current_rules

    mesh = current_rules().mesh
    g = st.g0_fn()
    pool = tuple(st.pool_row(g, i) for i in range(st.traffic["pool"]))
    step = ref.replay_round(st.hyper, dtype, mesh)
    widest = ref.widest_gap(mesh)
    out = {"weights": [], "globals": {}}
    for r, rec in enumerate(st.rounds):
        idx = np.asarray([u[0] for u in rec["uploads"]], np.int32)
        sz = np.asarray([st.sizes[u[1]] for u in rec["uploads"]], np.float32)
        tau = np.asarray([r - u[2] for u in rec["uploads"]], np.float32)
        g, w = step(g, pool, idx, sz, tau)
        out["weights"].append(np.asarray(w))
        if r + 1 not in st.kept_globals:
            continue
        if against is None:
            out["globals"][r + 1] = np.asarray(g)
            continue
        host = against[r + 1]
        out["globals"][r + 1] = (
            tuple(float(x) for x in widest(jax.device_put(host, g.sharding),
                                           g))
            if host.shape == g.shape else (float("inf"),) * 3)
    return out


def gaps(got_weights: list, want_weights: list, globals_: dict,
         scale: int) -> dict:
    """``global_gap``: the widest gap of a kept global, over the largest
    magnitude of the reference's (element ``scale`` of each reading);
    ``weights_gap``: the widest gap of a round's weights.  A shape that
    differs reads infinite."""
    w_gap = g_gap = 0.0
    for a, b in zip(got_weights, want_weights):
        w_gap = max(w_gap, float(np.max(np.abs(a - b)))
                    if a.shape == b.shape else float("inf"))
    if len(got_weights) != len(want_weights):
        w_gap = float("inf")
    for reading in globals_.values():
        g_gap = max(g_gap, reading[0] / reading[scale])
    return {"global_gap": g_gap, "weights_gap": w_gap}


def check(st: State, detail: bool = False) -> dict:
    import jax.numpy as jnp

    want = reference(st, jnp.float32, against=st.kept_globals)
    return gaps([r["weights"] for r in st.rounds], want["weights"],
                want["globals"], scale=2)


def control(st: State, detail: bool = False) -> dict:
    """The reference in bfloat16 put in the program's place."""
    import jax.numpy as jnp

    want = reference(st, jnp.float32)
    got = reference(st, jnp.bfloat16, against=want["globals"])
    return gaps(got["weights"], want["weights"], got["globals"], scale=1)
