"""Streaming uplink ingest subsystem: wire-format chunk round-trips, the
IngestSession-vs-monolithic-pack identity, bf16 buffer mode, sync-wait spill
through chunked writes, mid-stream checkpointing, and the bandwidth model."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buffer import Update, UpdateBuffer
from repro.core.server import FLConfig, SeaflServer
from repro.runtime.transport import (
    CHUNK_HEADER_BYTES, FlatErrorFeedback, IngestSession, decode_chunk,
    encode_flat, encode_update, make_wire_format,
)

RNG = np.random.default_rng(42)


def flat_vec(p, rng=RNG):
    return jnp.asarray(rng.normal(size=p).astype(np.float32))


# ------------------------------------------------------------- wire format

def test_make_wire_format_specs():
    assert make_wire_format(None).scheme == "f32"
    assert make_wire_format("none").scheme == "f32"
    assert make_wire_format("f32").scheme == "f32"
    assert make_wire_format("bf16").scheme == "bf16"
    fmt = make_wire_format("topk:0.25", chunk_elems=128)
    assert fmt.scheme == "topk" and fmt.topk_ratio == 0.25
    assert fmt.chunk_elems == 128
    assert make_wire_format("int8").scheme == "int8"
    with pytest.raises(ValueError):
        make_wire_format("zstd")
    with pytest.raises(ValueError):
        make_wire_format("topk:1.5")


def test_payload_bytes_accounting():
    """Wire bytes include per-chunk framing and scale with the scheme."""
    p, ce = 1000, 256
    f32 = make_wire_format("f32", ce)
    bf16 = make_wire_format("bf16", ce)
    topk = make_wire_format("topk:0.1", ce)
    int8 = make_wire_format("int8", ce)
    n_chunks = 4   # 1000 = 3*256 + 232
    assert f32.payload_bytes(p) == 4 * p + n_chunks * CHUNK_HEADER_BYTES
    assert bf16.payload_bytes(p) == 2 * p + n_chunks * CHUNK_HEADER_BYTES
    assert int8.payload_bytes(p) == p + 4 * n_chunks \
        + n_chunks * CHUNK_HEADER_BYTES
    kept = 3 * 25 + 23
    assert topk.payload_bytes(p) == 8 * kept + n_chunks * CHUNK_HEADER_BYTES
    # the whole point: compressed payloads are strictly smaller
    assert topk.payload_bytes(p) < int8.payload_bytes(p) \
        < bf16.payload_bytes(p) < f32.payload_bytes(p)


# ----------------------------------------------------- chunk round-trips

def reassemble(chunks, fmt, p):
    out = np.zeros(p, np.float32)
    for c in chunks:
        out[c.start:c.start + c.length] = np.asarray(decode_chunk(c, fmt))
    return out


@pytest.mark.parametrize("p,chunk_elems", [(1000, 256), (256, 256), (7, 16)])
def test_f32_chunks_bit_exact(p, chunk_elems):
    x = flat_vec(p)
    fmt = make_wire_format("f32", chunk_elems)
    chunks = encode_flat(x, fmt)
    np.testing.assert_array_equal(reassemble(chunks, fmt, p), np.asarray(x))


def test_bf16_chunks_match_bf16_cast():
    x = flat_vec(500)
    fmt = make_wire_format("bf16", 128)
    got = reassemble(encode_flat(x, fmt), fmt, 500)
    np.testing.assert_array_equal(
        got, np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))


def test_topk_chunks_keep_largest_per_chunk():
    p, ce, ratio = 512, 128, 0.1
    x = flat_vec(p)
    fmt = make_wire_format(f"topk:{ratio}", ce)
    chunks = encode_flat(x, fmt)
    k = int(ce * ratio)
    for c in chunks:
        win = np.abs(np.asarray(x[c.start:c.start + c.length]))
        dec = np.asarray(decode_chunk(c, fmt))
        nz = dec != 0
        assert np.count_nonzero(nz) <= k
        thresh = np.sort(win)[-k]
        assert (win[nz] >= thresh - 1e-6).all()
        np.testing.assert_allclose(dec[nz], np.asarray(x)[c.start:c.start
                                                          + c.length][nz])


def test_int8_chunks_error_bound():
    p, ce = 700, 256
    x = flat_vec(p)
    fmt = make_wire_format("int8", ce)
    for c in encode_flat(x, fmt):
        win = np.asarray(x[c.start:c.start + c.length])
        dec = np.asarray(decode_chunk(c, fmt))
        scale = np.max(np.abs(win)) / 127.0
        assert np.max(np.abs(win - dec)) <= scale * 0.5 + 1e-6


def test_flat_error_feedback_accumulates_everything():
    """Sum of EF-compressed uploads converges to the sum of true deltas."""
    rng = np.random.default_rng(0)
    p = 300
    delta = flat_vec(p, rng)
    base = jnp.zeros(p)
    fmt = make_wire_format("topk:0.2", 128)
    ef = FlatErrorFeedback()
    acc = np.zeros(p)
    T = 30
    for _ in range(T):
        payload = encode_update(0, 0, 1, base + delta, fmt, base, ef)
        acc += reassemble(payload.chunks, fmt, p)
    target = np.asarray(delta) * T
    rel = np.linalg.norm(acc - target) / np.linalg.norm(target)
    assert rel < 0.2


def test_ingest_rejects_out_of_order_and_incomplete():
    buf = UpdateBuffer(2, 64)
    fmt = make_wire_format("f32", 16)
    chunks = encode_flat(flat_vec(64), fmt)
    slot = buf.reserve(Update(0, 1, 0, 1))
    sess = IngestSession(buf, slot, fmt)
    sess.write(chunks[0])
    with pytest.raises(ValueError):
        sess.write(chunks[2])          # skipped chunk 1
    with pytest.raises(ValueError):
        sess.finish()                  # coverage incomplete
    for c in chunks[1:]:
        sess.write(c)
    assert sess.finish() == fmt.payload_bytes(64)


# --------------------------------------------------- server-level identity

def make_server(algorithm="seafl", n=12, M=6, K=3, beta=4.0, **kw):
    params = {"w": jnp.zeros((11, 7)), "b": {"c": jnp.zeros((13,))}}
    cfg = FLConfig(algorithm=algorithm, n_clients=n, concurrency=M,
                   buffer_size=K, staleness_limit=beta, seed=0, **kw)
    return SeaflServer(cfg, params, {i: 10 * (i + 1) for i in range(n)})


def perturbed(base, rng, scale=0.1):
    return jax.tree.map(lambda x: x + scale * jnp.asarray(
        rng.normal(size=x.shape).astype(np.float32)), base)


def test_chunked_ingest_bit_identical_to_monolithic_pack():
    """Acceptance: the f32 chunked path writes a buffer bit-identical to
    ParamPacker.pack (across a chunk size that forces many partial writes)."""
    s = make_server(chunk_elems=13)            # P = 90 -> 7 chunks
    s.start()
    rng = np.random.default_rng(1)
    sent = []
    for _ in range(s.cfg.buffer_size - 1):     # stop short of the trigger
        cid = sorted(s.active)[0]
        w = perturbed(s.params_at(s.active[cid]), rng)
        sent.append(np.asarray(s.packer.pack(w)))
        assert s.on_update(cid, w, n_epochs=5) is None
    got = np.asarray(s.buffer.stacked_flat())
    np.testing.assert_array_equal(got, np.stack(sent))


def test_streaming_ingest_equals_atomic_ingest():
    """Feeding chunks one call at a time through begin/ingest/finish gives
    the same buffer and aggregation as ingest_payload."""
    sa, sb = make_server(), make_server()
    sa.start(), sb.start()
    rng_a, rng_b = np.random.default_rng(2), np.random.default_rng(2)
    for _ in range(4):
        for s, rng, streaming in ((sa, rng_a, False), (sb, rng_b, True)):
            cid = sorted(s.active)[0]
            w = perturbed(s.params_at(s.active[cid]), rng)
            payload = s.encode_update(cid, w, 5)
            if streaming:
                s.begin_ingest(payload.cid, payload.version,
                               payload.n_epochs)
                for c in payload.chunks:
                    s.ingest_chunk(payload.cid, c)
                s.finish_ingest(payload.cid)
            else:
                s.ingest_payload(payload)
    np.testing.assert_array_equal(np.asarray(sa.global_flat),
                                  np.asarray(sb.global_flat))
    assert sa.bytes_uploaded == sb.bytes_uploaded > 0


def test_uncompressed_uploads_counted_in_bytes_uploaded():
    """Satellite: compression=None payloads must count wire bytes too."""
    s = make_server()          # compression=None -> raw f32 wire
    s.start()
    cid = sorted(s.active)[0]
    w = perturbed(s.params_at(s.active[cid]), np.random.default_rng(0))
    s.on_update(cid, w, n_epochs=5)
    assert s.bytes_uploaded == s.wire.payload_bytes(s.packer.size)
    assert s.bytes_uploaded > 4 * s.packer.size   # headers included


def test_sync_wait_spill_through_chunked_writes():
    """While sync-wait holds aggregation the slot buffer grows past K,
    every spilled update lands bit-exact through the chunked path, and the
    eventual aggregation consumes all of them."""
    s = make_server(chunk_elems=17)
    s.start()
    frozen = sorted(s.active)[0]
    rng = np.random.default_rng(3)
    for _ in range(60):
        if len(s.buffer) > s.buffer.capacity + 1:   # well past K
            break
        live = [c for c in sorted(s.active) if c != frozen]
        if not live:
            break
        # stalest non-frozen first, so only `frozen` ever blocks aggregation
        cid = min(live, key=lambda c: (s.active[c], c))
        w = perturbed(s.params_at(s.active[cid]), rng)
        before = len(s.buffer)
        ev = s.on_update(cid, w, n_epochs=5)
        if ev is None and before >= s.buffer.capacity:
            # spilled row must be bit-exact vs the monolithic pack
            np.testing.assert_array_equal(
                np.asarray(s.buffer.stacked_flat()[before]),
                np.asarray(s.packer.pack(w)))
    n_spilled = len(s.buffer)
    assert n_spilled > s.cfg.buffer_size and s._blocked_by_stale()
    # the frozen client finally reports: one aggregation drains everything
    w = perturbed(s.params_at(s.active[frozen]), rng)
    ev = s.on_update(frozen, w, n_epochs=5)
    assert ev is not None
    assert len(ev.contributors) == n_spilled + 1 > s.cfg.buffer_size
    assert len(s.buffer) == 0


def test_concurrent_streams_finish_out_of_order():
    """Two clients stream concurrently; the later-opened one finishes first.
    Slots are physical rows, so commits land in any order and stacked_flat
    returns arrival (commit) order."""
    s = make_server(chunk_elems=13)
    s.start()
    rng = np.random.default_rng(11)
    cids = sorted(s.active)[:2]
    payloads = {}
    for cid in cids:
        w = perturbed(s.params_at(s.active[cid]), rng)
        payloads[cid] = (s.encode_update(cid, w, 5), np.asarray(s.packer.pack(w)))
        s.begin_ingest(cid, payloads[cid][0].version, 5)
        for c in payloads[cid][0].chunks:
            s.ingest_chunk(cid, c)
    # finish in reverse open order
    assert s.finish_ingest(cids[1]) is None
    assert s.finish_ingest(cids[0]) is None
    got = np.asarray(s.buffer.stacked_flat())
    np.testing.assert_array_equal(got[0], payloads[cids[1]][1])
    np.testing.assert_array_equal(got[1], payloads[cids[0]][1])
    assert [u.client_id for u in s.buffer.updates()] == [cids[1], cids[0]]


def test_failed_client_mid_stream_releases_slot():
    """mark_failed during a chunked upload recycles the reserved row; the
    server keeps aggregating normally afterwards."""
    s = make_server(chunk_elems=13)
    s.start()
    rng = np.random.default_rng(12)
    dead = sorted(s.active)[0]
    payload = s.encode_update(
        dead, perturbed(s.params_at(s.active[dead]), rng), 5)
    s.begin_ingest(dead, payload.version, 5)
    s.ingest_chunk(dead, payload.chunks[0])
    s.mark_failed(dead)
    assert not s.buffer.streaming          # reservation released
    # the fleet continues: enough uploads to trigger an aggregation
    aggregated = False
    for _ in range(2 * s.cfg.buffer_size):
        live = sorted(s.active)
        if not live:
            break
        cid = live[0]
        w = perturbed(s.params_at(s.active[cid]), rng)
        if s.on_update(cid, w, n_epochs=5) is not None:
            aggregated = True
            break
    assert aggregated


def test_incomplete_finish_is_recoverable():
    """finish_ingest on a truncated stream raises but keeps the session, so
    the driver can deliver the missing chunks or abort cleanly."""
    s = make_server(chunk_elems=13)
    s.start()
    rng = np.random.default_rng(13)
    cid = sorted(s.active)[0]
    payload = s.encode_update(
        cid, perturbed(s.params_at(s.active[cid]), rng), 5)
    s.begin_ingest(cid, payload.version, 5)
    for c in payload.chunks[:-1]:
        s.ingest_chunk(cid, c)
    with pytest.raises(ValueError):
        s.finish_ingest(cid)
    # path A: the missing chunk arrives late — the upload completes
    s.ingest_chunk(cid, payload.chunks[-1])
    s.finish_ingest(cid)
    assert len(s.buffer) == 1 and not s.buffer.streaming
    # path B: a second truncated stream is aborted — slot recycled
    cid2 = sorted(s.active)[0]
    p2 = s.encode_update(
        cid2, perturbed(s.params_at(s.active[cid2]), rng), 5)
    s.begin_ingest(cid2, p2.version, 5)
    s.ingest_chunk(cid2, p2.chunks[0])
    s.abort_ingest(cid2)
    assert not s.buffer.streaming
    assert cid2 in s.active                # still in flight; will re-send


def test_aggregation_proceeds_while_another_stream_open():
    """A mid-stream upload no longer holds aggregation: its reserved row
    survives the drain and commits into the next round's buffer."""
    s = make_server(chunk_elems=13)
    s.start()
    rng = np.random.default_rng(14)
    streamer = sorted(s.active)[0]
    w_stream = perturbed(s.params_at(s.active[streamer]), rng)
    ps = s.encode_update(streamer, w_stream, 5)
    s.begin_ingest(streamer, ps.version, 5)
    s.ingest_chunk(streamer, ps.chunks[0])
    ev = None
    for _ in range(s.cfg.buffer_size):
        cid = [c for c in sorted(s.active) if c != streamer][0]
        w = perturbed(s.params_at(s.active[cid]), rng)
        ev = s.on_update(cid, w, n_epochs=5)
    assert ev is not None and len(s.buffer) == 0   # aggregated + drained
    for c in ps.chunks[1:]:
        s.ingest_chunk(streamer, c)
    s.finish_ingest(streamer)
    assert len(s.buffer) == 1
    np.testing.assert_array_equal(np.asarray(s.buffer.stacked_flat()[0]),
                                  np.asarray(s.packer.pack(w_stream)))


# ------------------------------------------------------------- bf16 buffer

def test_bf16_buffer_halves_bytes_with_agg_parity():
    """Acceptance: bf16 slots halve buffer HBM; aggregation stays within
    1e-2 of the f32-buffer result (f32 accumulation in the kernels)."""
    s32 = make_server(buffer_dtype="float32")
    s16 = make_server(buffer_dtype="bfloat16")
    assert s16.buffer.hbm_bytes * 2 == s32.buffer.hbm_bytes
    s32.start(), s16.start()
    rng32, rng16 = np.random.default_rng(4), np.random.default_rng(4)
    evs = []
    for s, rng in ((s32, rng32), (s16, rng16)):
        for _ in range(s.cfg.buffer_size):
            cid = sorted(s.active)[0]
            w = perturbed(s.params_at(s.active[cid]), rng, scale=0.3)
            ev = s.on_update(cid, w, n_epochs=5)
        evs.append(ev)
    assert evs[0] is not None and evs[1] is not None
    np.testing.assert_allclose(np.asarray(s16.global_flat),
                               np.asarray(s32.global_flat),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(evs[1].weights, evs[0].weights, atol=1e-2)


@pytest.mark.parametrize("algorithm", ["fedavg", "fedbuff", "fedasync"])
def test_bf16_buffer_parity_baselines(algorithm, ):
    s32 = make_server(algorithm, buffer_dtype="float32", beta=None)
    s16 = make_server(algorithm, buffer_dtype="bfloat16", beta=None)
    s32.start(), s16.start()
    rng32, rng16 = np.random.default_rng(5), np.random.default_rng(5)
    for s, rng in ((s32, rng32), (s16, rng16)):
        for _ in range(6):
            cid = sorted(s.active)[0]
            w = perturbed(s.params_at(s.active[cid]), rng, scale=0.3)
            s.on_update(cid, w, n_epochs=5)
    np.testing.assert_allclose(np.asarray(s16.global_flat),
                               np.asarray(s32.global_flat),
                               atol=1e-2, rtol=1e-2)


# ----------------------------------------------------- checkpoint semantics

def drive_to_nonempty_blocked_buffer(s, rng):
    """Freeze one client so sync-wait engages with a non-empty buffer.
    Always completes the stalest non-frozen client, so when the frozen one
    finally reports nothing else holds aggregation back."""
    frozen = sorted(s.active)[0]
    for _ in range(60):
        # filled to K while blocked: the frozen client's report will trigger
        if len(s.buffer) >= s.buffer.capacity and s._blocked_by_stale():
            return frozen
        live = [c for c in sorted(s.active) if c != frozen]
        cid = min(live, key=lambda c: (s.active[c], c))
        w = perturbed(s.params_at(s.active[cid]), rng)
        s.on_update(cid, w, n_epochs=5)
    raise AssertionError("never reached blocked+non-empty state")


def test_checkpoint_preserves_buffer_under_sync_wait():
    """Satellite: a checkpoint taken while sync-wait blocks aggregation must
    persist the filled slots; the restored server aggregates identically."""
    s = make_server(beta=2.0, K=3)
    s.start()
    rng = np.random.default_rng(6)
    frozen = drive_to_nonempty_blocked_buffer(s, rng)
    assert len(s.buffer) > 0
    state, trees = s.state_dict(), s.checkpoint_trees()
    assert any(k.startswith("slot") for k in trees)

    s2 = make_server(beta=2.0, K=3)
    s2.load_state(state, trees)
    assert len(s2.buffer) == len(s.buffer)
    np.testing.assert_array_equal(np.asarray(s2.buffer.stacked_flat()),
                                  np.asarray(s.buffer.stacked_flat()))
    assert [u.client_id for u in s2.buffer.updates()] == \
        [u.client_id for u in s.buffer.updates()]

    # unblock both the same way: the frozen client finally reports
    rng_a, rng_b = np.random.default_rng(7), np.random.default_rng(7)
    for srv, rng_x in ((s, rng_a), (s2, rng_b)):
        w = perturbed(srv.params_at(srv.active[frozen]), rng_x)
        ev = srv.on_update(frozen, w, n_epochs=5)
        assert ev is not None, "frozen client's report must unblock"
    np.testing.assert_allclose(np.asarray(s2.global_flat),
                               np.asarray(s.global_flat), atol=1e-6)


def test_checkpoint_mid_stream_drops_pending_keeps_committed():
    """Satellite: a checkpoint taken mid-chunk-stream persists committed
    slots only; the streaming client stays active (it will be re-sent)."""
    s = make_server(chunk_elems=13)
    s.start()
    rng = np.random.default_rng(8)
    # one committed upload
    cid0 = sorted(s.active)[0]
    s.on_update(cid0, perturbed(s.params_at(s.active[cid0]), rng), 5)
    # one mid-stream upload: half the chunks written
    cid1 = sorted(s.active)[0]
    payload = s.encode_update(
        cid1, perturbed(s.params_at(s.active[cid1]), rng), 5)
    s.begin_ingest(payload.cid, payload.version, payload.n_epochs)
    for c in payload.chunks[: len(payload.chunks) // 2]:
        s.ingest_chunk(payload.cid, c)
    assert s.buffer.streaming

    state, trees = s.state_dict(), s.checkpoint_trees()
    assert len(state["buffer"]) == 1          # committed only
    s2 = make_server(chunk_elems=13)
    s2.load_state(state, trees)
    assert len(s2.buffer) == 1 and not s2.buffer.streaming
    assert cid1 in s2.active                  # will be re-dispatched/re-sent
    # the restored server ingests cid1's full upload cleanly
    p2 = s2.encode_update(
        cid1, perturbed(s2.params_at(s2.active[cid1]), rng), 5)
    s2.ingest_payload(p2)
    assert len(s2.buffer) == 2


def test_load_state_guards_stale_ef_residuals():
    """Satellite: restoring an EF-carrying checkpoint into compression=None
    must warn and drop residuals instead of crashing on the next update."""
    s = make_server(compression="topk:0.25")
    s.start()
    rng = np.random.default_rng(9)
    for _ in range(3):
        cid = sorted(s.active)[0]
        s.on_update(cid, perturbed(s.params_at(s.active[cid]), rng), 5)
    state, trees = s.state_dict(), s.checkpoint_trees()
    assert any(k.startswith("ef") for k in trees)

    s2 = make_server()                        # compression=None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        s2.load_state(state, trees)
    assert any("residual" in str(w.message) for w in caught)
    assert not s2._ef
    # next update must not crash (this is the seed bug: ErrorFeedback(None))
    cid = sorted(s2.active)[0]
    s2.on_update(cid, perturbed(s2.params_at(s2.active[cid]), rng), 5)


def test_load_state_restores_legacy_pytree_residuals():
    """Pre-transport checkpoints stored per-leaf residual pytrees; they must
    pack losslessly into the flat EF."""
    s = make_server(compression="topk:0.25")
    s.start()
    rng = np.random.default_rng(10)
    for _ in range(2):
        cid = sorted(s.active)[0]
        s.on_update(cid, perturbed(s.params_at(s.active[cid]), rng), 5)
    state, trees = s.state_dict(), s.checkpoint_trees()
    legacy = {k: (s.packer.unpack(v) if k.startswith("ef") else v)
              for k, v in trees.items()}
    s2 = make_server(compression="topk:0.25")
    s2.load_state(state, legacy)
    for cid in s._ef:
        np.testing.assert_allclose(np.asarray(s2._ef[cid].residual),
                                   np.asarray(s._ef[cid].residual),
                                   atol=1e-7)


# ----------------------------------------------------------- bandwidth model

def _bw_experiment(compression, up_mbps=0.1, rounds=6):
    from repro.experiment import ExperimentConfig, run_experiment
    from repro.runtime.simulator import SimConfig
    fl = FLConfig(algorithm="seafl", n_clients=8, concurrency=4,
                  buffer_size=2, staleness_limit=4, local_epochs=2,
                  local_lr=0.05, batch_size=16, seed=3,
                  compression=compression)
    cfg = ExperimentConfig(
        dataset="tiny", n_train=400, n_test=80, model="mlp", fl=fl,
        sim=SimConfig(speed_model="pareto", base_epoch_time=1.0, seed=3,
                      bandwidth_model="pareto", up_mbps=up_mbps,
                      down_mbps=50.0),
        seed=3)
    return run_experiment(cfg, max_rounds=rounds)


def test_upload_time_scales_with_wire_bytes():
    """Acceptance: with the bandwidth model on, topk:0.1 uploads finish the
    same number of rounds measurably faster than uncompressed f32."""
    _, h_raw = _bw_experiment(None)
    _, h_topk = _bw_experiment("topk:0.1")
    assert h_raw and h_topk
    t_raw, t_topk = h_raw[-1]["time"], h_topk[-1]["time"]
    assert h_raw[-1]["round"] == h_topk[-1]["round"]
    # topk:0.1 ships ~5x fewer bytes; on a slow uplink that must dominate
    assert t_topk < 0.8 * t_raw, (t_raw, t_topk)
    assert h_topk[-1]["bytes"] < 0.3 * h_raw[-1]["bytes"]


def test_bandwidth_model_off_ignores_bytes():
    """Legacy behaviour pinned: with bandwidth_model='none', compressed and
    raw runs see identical simulated upload timing."""
    from repro.experiment import ExperimentConfig, run_experiment
    from repro.runtime.simulator import SimConfig

    def run(compression):
        fl = FLConfig(algorithm="seafl", n_clients=8, concurrency=4,
                      buffer_size=2, staleness_limit=4, local_epochs=2,
                      local_lr=0.05, batch_size=16, seed=3,
                      compression=compression)
        cfg = ExperimentConfig(dataset="tiny", n_train=400, n_test=80,
                               model="mlp", fl=fl,
                               sim=SimConfig(speed_model="pareto", seed=3),
                               seed=3)
        return run_experiment(cfg, max_rounds=4)

    _, h_raw = run(None)
    _, h_bf16 = run("bf16")
    assert [h["time"] for h in h_raw] == [h["time"] for h in h_bf16]


def test_crash_mid_transfer_drops_payload():
    """A client that crashes after training but before its last wire chunk
    lands must not be ingested: the payload dies with the transfer (legacy
    fixed-latency behaviour for fails inside the up_latency window)."""
    from repro.experiment import ExperimentConfig, build_experiment
    from repro.runtime.simulator import SimConfig
    fl = FLConfig(algorithm="seafl", n_clients=6, concurrency=3,
                  buffer_size=3, staleness_limit=None, local_epochs=2,
                  batch_size=16, seed=5)
    cfg = ExperimentConfig(dataset="tiny", n_train=300, n_test=60,
                           model="mlp", fl=fl,
                           sim=SimConfig(seed=5, up_latency=1.0,
                                         recover_after=2.0), seed=5)
    sim, _, _ = build_experiment(cfg)
    for cid in sim.server.start():
        sim._dispatch(cid)
    up = min((e for e in sim._heap if e.kind == "upload"),
             key=lambda e: (e.time, e.seq))
    cid = up.data["cid"]
    up.valid = False
    sim.now = up.time
    sim._handle_upload(cid)                       # trains + starts transfer
    deliver = sim._delivering[cid]
    assert deliver.time > sim.now
    bytes_before = sim.server.bytes_uploaded
    fail_at = (sim.now + deliver.time) / 2        # inside the transfer
    sim._push(fail_at, "fail", cid=cid)
    sim.run(max_time=fail_at + 1e-9)
    assert not deliver.valid                      # transfer killed
    assert cid not in sim.server.active           # marked failed
    assert sim.server.bytes_uploaded == bytes_before
    assert len(sim.server.buffer) == 0
    # and the fleet keeps making progress afterwards
    hist = sim.run(max_rounds=2)
    assert sim.server.round >= 1 and len(hist) >= 1


def test_transfer_window_organically_crashable():
    """Under the bandwidth model, slow transfers dominate a client's
    lifetime, so the per-dispatch crash hazard must extend into the
    transfer window (not just the training window)."""
    from repro.experiment import ExperimentConfig, build_experiment
    from repro.runtime.simulator import SimConfig
    fl = FLConfig(algorithm="seafl", n_clients=6, concurrency=3,
                  buffer_size=3, staleness_limit=None, local_epochs=1,
                  batch_size=16, seed=1)
    cfg = ExperimentConfig(
        dataset="tiny", n_train=300, n_test=60, model="mlp", fl=fl,
        sim=SimConfig(speed_model="pareto", base_epoch_time=0.05, seed=1,
                      bandwidth_model="pareto", up_mbps=0.01, down_mbps=50.0,
                      fail_prob=1.0, recover_after=1.0),
        seed=1)
    sim, _, _ = build_experiment(cfg)
    for cid in sim.server.start():
        sim._dispatch(cid)
    up = min((e for e in sim._heap if e.kind == "upload"),
             key=lambda e: (e.time, e.seq))
    cid = up.data["cid"]
    up.valid = False
    sim.now = up.time
    sim._handle_upload(cid)
    deliver = sim._delivering[cid]
    # transfer takes seconds while training took ~0.05 s: the hazard share
    # is ~1, so with fail_prob=1.0 a mid-transfer fail event must exist
    fails = [e for e in sim._heap if e.kind == "fail" and e.valid
             and e.data["cid"] == cid and sim.now < e.time <= deliver.time]
    assert fails, "no organic crash scheduled inside the transfer window"


def test_failures_with_bandwidth_model_do_not_deadlock():
    from repro.experiment import ExperimentConfig, run_experiment
    from repro.runtime.simulator import SimConfig
    fl = FLConfig(algorithm="seafl2", n_clients=10, concurrency=5,
                  buffer_size=2, staleness_limit=4, local_epochs=2,
                  batch_size=16, seed=2)
    cfg = ExperimentConfig(
        dataset="tiny", n_train=400, n_test=80, model="mlp", fl=fl,
        sim=SimConfig(speed_model="pareto", seed=2,
                      bandwidth_model="pareto", up_mbps=0.2, down_mbps=20.0,
                      fail_prob=0.25, recover_after=5.0),
        seed=2)
    sim, hist = run_experiment(cfg, max_rounds=8, max_time=5000)
    assert len(hist) >= 3
    assert np.isfinite(hist[-1]["time"])


def test_chunked_run_resumes_mid_transfer():
    """Checkpoint-chunked driving (repeated run() calls) must not
    re-dispatch a client whose payload is still on the wire."""
    from repro.experiment import ExperimentConfig, build_experiment
    from repro.runtime.simulator import SimConfig

    def build():
        fl = FLConfig(algorithm="seafl", n_clients=8, concurrency=4,
                      buffer_size=2, staleness_limit=4, local_epochs=2,
                      local_lr=0.05, batch_size=16, seed=3)
        cfg = ExperimentConfig(
            dataset="tiny", n_train=400, n_test=80, model="mlp", fl=fl,
            sim=SimConfig(speed_model="pareto", seed=3,
                          bandwidth_model="pareto", up_mbps=0.1,
                          down_mbps=50.0),
            seed=3)
        return build_experiment(cfg)[0]

    sim1 = build()
    h1 = sim1.run(max_rounds=6)
    sim2 = build()
    for stop in (2, 4, 6):                        # run() boundaries land
        h2 = sim2.run(max_rounds=stop)            # mid-transfer
    assert [h["round"] for h in h1] == [h["round"] for h in h2]
    assert [h["time"] for h in h1] == [h["time"] for h in h2]
    assert [h["bytes"] for h in h1] == [h["bytes"] for h in h2]


def test_history_records_cumulative_bytes():
    _, hist = _bw_experiment(None, rounds=4)
    bytes_seen = [h["bytes"] for h in hist]
    assert all(b > 0 for b in bytes_seen)
    assert bytes_seen == sorted(bytes_seen)


# ------------------------------------------------------------- pod sharding

def test_buffer_sharded_over_pod_axis():
    """With a 'pod' mesh axis active, the (K, P) buffer rows are placed over
    it per DEFAULT_RULES['buffer'] (multi-device via host-platform split)."""
    import subprocess, sys, os
    code = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import jax, numpy as np
from jax.sharding import Mesh
from repro.sharding import axis_rules
from repro.core.buffer import Update, UpdateBuffer

mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("pod", "data"))
with axis_rules(mesh):
    buf = UpdateBuffer(4, 64)
    spec = buf._buf.sharding.spec
    assert tuple(spec) == ("pod", None), spec
    # chunked writes and spill-growth keep the placement
    import jax.numpy as jnp
    for i in range(6):
        buf.add(Update(i, 1, 0, 1), jnp.ones(64) * i)
    assert tuple(buf._buf.sharding.spec) == ("pod", None)
    got = np.asarray(buf.stacked_flat())
    np.testing.assert_array_equal(got, np.outer(np.arange(6), np.ones(64)))
print("SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert "SHARDED_OK" in out.stdout, out.stderr


# ------------------------------------------------- one write program per upload

def _bench_ingest_modules():
    """``MODULES`` of the benchmark's ``ingest_device_ms.server`` metric."""
    import runpy
    from pathlib import Path
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "metrics" / "ingest_device_ms.server.py")
    return runpy.run_path(str(path))["MODULES"]


@pytest.mark.parametrize("spec", [None, "int8"])
def test_ingest_payload_is_one_write_program_and_no_join(spec, monkeypatch):
    """Each ``ingest_payload`` dispatches the buffer's write program once
    and no chunk join, on a raw and on a delta-coded wire."""
    from repro.core import buffer as buffer_mod
    from repro.runtime import codecs

    s = make_server(K=4, compression=spec, chunk_elems=13)   # 7 windows
    s.start()
    rng = np.random.default_rng(5)
    joins, writes = [0], [0]
    join, write = codecs._join_chunks, buffer_mod._write_range

    def counted_join(*a):
        joins[0] += 1
        return join(*a)

    def counted_write(*a):
        writes[0] += 1
        return write(*a)

    monkeypatch.setattr(codecs, "_join_chunks", counted_join)
    monkeypatch.setattr(buffer_mod, "_write_range", counted_write)
    for i in range(s.cfg.buffer_size - 1):          # stop short of the trigger
        cid = sorted(s.active)[0]
        payload = s.encode_update(
            cid, perturbed(s.params_at(s.active[cid]), rng), 5)
        joins[0] = writes[0] = 0
        assert s.ingest_payload(payload) is None
        assert (joins[0], writes[0]) == (0, 1)


def test_write_program_compiles_once_per_model():
    """Five uploads of one model: one entry in the write program's jit
    cache (the window count and the tail length are fixed by P and the
    chunk size; the slot and start are traced scalars)."""
    from repro.core import buffer as buffer_mod

    s = make_server(n=12, M=6, K=6, chunk_elems=11)   # P = 90: 9 windows
    s.start()
    rng = np.random.default_rng(6)
    buffer_mod._write_range.clear_cache()
    for _ in range(5):
        cid = sorted(s.active)[0]
        assert s.on_update(cid, perturbed(s.params_at(s.active[cid]), rng),
                           n_epochs=5) is None
    assert buffer_mod._write_range._cache_size() == 1


@pytest.mark.parametrize("buffer_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("spec", ["f32", "bf16", "topk:0.25", "int8"])
def test_write_all_matches_chunk_by_chunk_write(spec, buffer_dtype):
    """Slot contents after ``write_all`` are bit-identical to chunk-by-chunk
    ``write``, with a short tail window, a P that is not a multiple of 128,
    more windows than one in-program join takes, and a run split in two so
    the second write starts mid-row."""
    rng = np.random.default_rng(12)
    P, ce = 1000, 24                               # 41 full windows + 16
    fmt = make_wire_format(spec, ce)
    base = flat_vec(P, rng)
    vec = base + 0.1 * flat_vec(P, rng)
    chunks = encode_update(0, 0, 1, vec, fmt,
                           base_flat=base if fmt.delta_coded else None).chunks
    assert chunks[-1].length == 16 and P % 128
    rows = []
    for fused in (False, True):
        buf = UpdateBuffer(2, P, dtype=buffer_dtype)
        buf.reserve(Update(9, 1, 0, 1))            # the upload lands in row 1
        slot = buf.reserve(Update(0, 1, 0, 1))
        sess = IngestSession(buf, slot, fmt,
                             base_flat=base if fmt.delta_coded else None)
        if fused:
            sess.write_all(chunks[:7])
            sess.write_all(chunks[7:])
        else:
            for c in chunks:
                sess.write(c)
        assert sess.complete
        rows.append(np.asarray(buf._buf[slot].astype(jnp.float32)))
    np.testing.assert_array_equal(rows[0], rows[1])


def test_write_program_is_read_by_the_ingest_device_metric():
    """The lowered write program's module name is one that the benchmark's
    ``ingest_device_ms.server`` reads."""
    from repro.core.buffer import _write_range

    buf = jnp.zeros((2, 40), jnp.float32)
    parts = (jnp.ones((16,)), jnp.ones((16,)), jnp.ones((8,)))
    for base in (None, jnp.zeros((40,))):
        first = _write_range.lower(buf, np.int32(0), np.int32(0), parts,
                                   base).as_text().splitlines()[0]
        name = first.split()[1].lstrip("@")
        assert name == "jit__write_range"
        assert any(m in name for m in _bench_ingest_modules())


@pytest.mark.parametrize("spec", [None, "topk:0.25"])
def test_fused_write_counters_equal_commits_and_windows(spec):
    """With telemetry on, ``ingest.fused_writes`` equals ``ingest.commits``
    and ``ingest.fused_chunks`` the windows sent."""
    s = make_server(K=3, compression=spec, chunk_elems=13, telemetry=True)
    s.start()
    rng = np.random.default_rng(7)
    windows = 0
    for _ in range(5):                             # one aggregation on the way
        cid = sorted(s.active)[0]
        payload = s.encode_update(
            cid, perturbed(s.params_at(s.active[cid]), rng), 5)
        windows += sum(1 for c in payload.chunks if c.length)
        s.ingest_payload(payload)
    c = s.tel.snapshot()["counters"]
    assert c["ingest.fused_writes"] == c["ingest.commits"] == 5
    assert c["ingest.fused_chunks"] == windows == 5 * 7
