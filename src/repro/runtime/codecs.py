"""Shared chunk-codec layer for the bidirectional wire stack.

Every byte that moves between server and client — uplink client updates
(runtime/transport.py) and downlink model dispatches (runtime/dispatch.py)
— travels as fixed-size chunks of the flat ``(P,)`` ``ParamPacker`` vector,
encoded by exactly one of the codecs registered here.  Both directions used
to carry private copies of the scheme logic; this module is the single
registry they now consume, so a new wire scheme (or an adaptive top-k
ratio, runtime/policy.py) is implemented and tested once.

Codecs (``CODECS`` registry, keyed by scheme name):

  f32   — raw f32 chunks (4 B/elem).  Bit-exact passthrough; the
          no-compression baseline in both directions.
  bf16  — bf16 chunks (2 B/elem), ~3 decimal digits.
  topk  — per-chunk top-k sparsification (idx i32 + val f32 = 8 B per kept
          elem) of a *delta*; lossy, so carriers run error feedback.
  int8  — per-chunk symmetric int8 quantisation of a delta (1 B/elem +
          4 B scale); lossy, EF-carried.

Delta-coded schemes (``delta_coded=True``) encode a difference against a
base both ends share — the dispatch-version global on the uplink, a ring
version on the downlink — and their encode error is what the per-client
error-feedback residuals (``FlatErrorFeedback`` here; server-side dispatch
residuals in ``DispatchSession``) accumulate: ``encode_error`` is the
per-payload EF hook both directions call.

Every chunk carries ``CHUNK_HEADER_BYTES`` of framing (seq, offset, length,
scheme tag) counted into its wire size, so the simulator's bandwidth model
charges real bytes, not idealised payload bytes.

Spec strings (``parse_spec``): ``None`` | ``'none'`` | ``'f32'`` |
``'bf16'`` | ``'topk[:<ratio>]'`` | ``'int8'`` — one validated grammar for
``FLConfig.compression``, ``FLConfig.dispatch_compression`` and the legacy
per-leaf compressor factory, so the error messages can no longer diverge.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "CHUNK_HEADER_BYTES",
    "DEFAULT_CHUNK_ELEMS",
    "Chunk",
    "ChunkCodec",
    "CODECS",
    "WireFormat",
    "parse_spec",
    "make_wire_format",
    "encode_chunk",
    "decode_chunk",
    "decode_concat",
    "encode_flat",
    "encode_flat_batch",
    "encode_error",
    "FlatErrorFeedback",
]

# seq:u32 | start:u64 | length:u32  — fixed framing per chunk
CHUNK_HEADER_BYTES = 16

DEFAULT_CHUNK_ELEMS = 1 << 16


@dataclass
class Chunk:
    """One wire chunk: a contiguous [start, start+length) window of the
    flat (P,) vector, encoded per the carrying WireFormat."""
    seq: int
    start: int
    length: int
    payload: Any                 # scheme-specific device array(s)
    nbytes: int                  # wire size incl. CHUNK_HEADER_BYTES


# --------------------------------------------------------------- kernels
# jit'd per (scheme, chunk length); at most two lengths occur per P (full
# chunks + one tail), so compile count stays tiny.

@jax.jit
def _enc_bf16(x):
    return x.astype(jnp.bfloat16)


@partial(jax.jit, static_argnames=("k",))
def _enc_topk(x, k):
    xf = x.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(xf), k)
    return {"idx": idx.astype(jnp.int32), "val": xf[idx]}


@jax.jit
def _enc_int8(x):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


@partial(jax.jit, static_argnames=("k",))
def _enc_topk_batch(x, k):
    """Row-wise _enc_topk over a (B, n) stack — one fused pass for a whole
    batch of same-window encodes (resync batching).  vmap of the exact
    per-row computation, so each row's idx/val are bit-identical to
    ``_enc_topk`` on that row alone."""

    def one(row):
        rf = row.astype(jnp.float32)
        _, idx = jax.lax.top_k(jnp.abs(rf), k)
        return {"idx": idx.astype(jnp.int32), "val": rf[idx]}

    return jax.vmap(one)(x)


@jax.jit
def _enc_int8_batch(x):
    """Row-wise _enc_int8 over a (B, n) stack: per-row max/abs scale, so
    each row quantises bit-identically to the unbatched kernel."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale[:, None]), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


@jax.jit
def _dec_bf16(x):
    return x.astype(jnp.float32)


@partial(jax.jit, static_argnames=("n",))
def _dec_topk(idx, val, n):
    return jnp.zeros((n,), jnp.float32).at[idx].set(val)


@jax.jit
def _dec_int8(q, scale):
    return q.astype(jnp.float32) * scale


# --------------------------------------------------------------- registry

class ChunkCodec:
    """One wire scheme: encode/decode of a flat f32 window + its byte law.

    ``delta_coded`` marks lossy difference codecs: they need a shared base
    on both ends and an error-feedback carrier for their encode error.
    Stateless — per-payload parameters (the top-k ratio) ride on the
    :class:`WireFormat`.
    """

    name: str = ""
    delta_coded: bool = False

    def body_bytes(self, n: int, fmt: "WireFormat") -> int:
        """Wire bytes of one n-element chunk body (header excluded)."""
        raise NotImplementedError

    def encode(self, x: jnp.ndarray, fmt: "WireFormat") -> Any:
        raise NotImplementedError

    def decode(self, payload: Any, length: int,
               fmt: "WireFormat") -> jnp.ndarray:
        raise NotImplementedError

    def encode_batch(self, x: jnp.ndarray, fmt: "WireFormat") -> Any:
        """Encode a (B, n) stack of same-window slices in one pass.

        Row ``i`` of the result (via :meth:`split_batch`) must be
        *bit-identical* to ``encode(x[i], fmt)`` — batching is a pure
        dispatch-count amortisation, never a semantic change.  The default
        falls back to row-by-row encode, so a new codec is correct before
        it is fast."""
        return [self.encode(x[i], fmt) for i in range(int(x.shape[0]))]

    def split_batch(self, payload: Any, i: int) -> Any:
        """Row ``i`` of an :meth:`encode_batch` result, in the same layout
        :meth:`decode` expects for a single chunk."""
        return payload[i]


CODECS: dict[str, ChunkCodec] = {}


def _register(codec: ChunkCodec) -> ChunkCodec:
    CODECS[codec.name] = codec
    return codec


class _F32Codec(ChunkCodec):
    name = "f32"

    def body_bytes(self, n, fmt):
        return 4 * n

    def encode(self, x, fmt):
        return x                                  # bit-exact passthrough

    def decode(self, payload, length, fmt):
        return payload

    def encode_batch(self, x, fmt):
        return x                                  # rows pass through

    def split_batch(self, payload, i):
        return payload[i]


class _Bf16Codec(ChunkCodec):
    name = "bf16"

    def body_bytes(self, n, fmt):
        return 2 * n

    def encode(self, x, fmt):
        return _enc_bf16(x)

    def decode(self, payload, length, fmt):
        return _dec_bf16(payload)

    def encode_batch(self, x, fmt):
        return _enc_bf16(x)                       # elementwise: rank-free

    def split_batch(self, payload, i):
        return payload[i]


class _TopkCodec(ChunkCodec):
    name = "topk"
    delta_coded = True

    def kept(self, n: int, fmt: "WireFormat") -> int:
        """Coefficients kept per n-element chunk (≥1: a chunk is never
        empty on the wire)."""
        return max(1, int(n * fmt.topk_ratio))

    def body_bytes(self, n, fmt):
        return 8 * self.kept(n, fmt)

    def encode(self, x, fmt):
        return _enc_topk(x, self.kept(int(x.shape[0]), fmt))

    def decode(self, payload, length, fmt):
        return _dec_topk(payload["idx"], payload["val"], length)

    def encode_batch(self, x, fmt):
        return _enc_topk_batch(x, self.kept(int(x.shape[1]), fmt))

    def split_batch(self, payload, i):
        return {"idx": payload["idx"][i], "val": payload["val"][i]}


class _Int8Codec(ChunkCodec):
    name = "int8"
    delta_coded = True

    def body_bytes(self, n, fmt):
        return n + 4

    def encode(self, x, fmt):
        return _enc_int8(x)

    def decode(self, payload, length, fmt):
        return _dec_int8(payload["q"], payload["scale"])

    def encode_batch(self, x, fmt):
        return _enc_int8_batch(x)

    def split_batch(self, payload, i):
        return {"q": payload["q"][i], "scale": payload["scale"][i]}


_register(_F32Codec())
_register(_Bf16Codec())
_register(_TopkCodec())
_register(_Int8Codec())


# ------------------------------------------------------------ wire format

@dataclass(frozen=True)
class WireFormat:
    """Static description of one wire encoding (either direction)."""
    scheme: str = "f32"                      # key into CODECS
    chunk_elems: int = DEFAULT_CHUNK_ELEMS   # elements per wire chunk
    topk_ratio: float = 0.1

    @property
    def codec(self) -> ChunkCodec:
        try:
            return CODECS[self.scheme]
        except KeyError:                       # pragma: no cover
            raise ValueError(f"unknown wire scheme {self.scheme!r}") from None

    @property
    def delta_coded(self) -> bool:
        """True when the wire carries delta-vs-base (needs base + EF)."""
        return self.codec.delta_coded

    def chunk_wire_bytes(self, n: int) -> int:
        """Wire bytes for one n-element chunk (header included)."""
        return self.codec.body_bytes(n, self) + CHUNK_HEADER_BYTES

    def payload_bytes(self, p: int) -> int:
        """Total wire bytes for a (p,)-element payload under this format."""
        total, off = 0, 0
        while off < p:
            n = min(self.chunk_elems, p - off)
            total += self.chunk_wire_bytes(n)
            off += n
        return total

    def kept_coeffs(self, p: int) -> Optional[int]:
        """Top-k coefficients a (p,)-element payload keeps (None for dense
        schemes) — the byte-budget resync policy's unit of account."""
        if self.scheme != "topk":
            return None
        codec: _TopkCodec = self.codec
        total, off = 0, 0
        while off < p:
            n = min(self.chunk_elems, p - off)
            total += codec.kept(n, self)
            off += n
        return total


def parse_spec(spec: Optional[str]) -> tuple[str, Optional[float]]:
    """Validate one wire-scheme spec -> ``(scheme, topk_ratio)``.

    Grammar: ``None`` | ``'none'`` | ``'f32'`` | ``'bf16'`` |
    ``'topk'`` | ``'topk:<ratio>'`` | ``'int8'``.  ``None``/``'none'``
    mean uncompressed and normalise to ``'f32'`` (the payload still has a
    real wire size, which is the whole point of the bandwidth model).
    The single source of truth for ``FLConfig.compression``,
    ``FLConfig.dispatch_compression`` and the legacy per-leaf compressor.
    """
    if spec is None or spec == "none":
        return "f32", None
    if not isinstance(spec, str):
        raise ValueError(f"wire scheme spec must be a string or None, "
                         f"got {type(spec).__name__}")
    scheme, _, arg = spec.partition(":")
    if scheme not in CODECS:
        raise ValueError(
            f"unknown wire scheme spec {spec!r} (expected None, 'none', "
            f"{', '.join(repr(s) for s in sorted(CODECS))}, "
            f"or 'topk:<ratio>')")
    if scheme != "topk":
        if arg:
            raise ValueError(f"wire scheme {scheme!r} takes no argument, "
                             f"got {spec!r}")
        return scheme, None
    if not arg:
        return "topk", 0.1
    try:
        ratio = float(arg)
    except ValueError:
        raise ValueError(f"topk ratio must be a number, got {arg!r}") \
            from None
    if not 0.0 < ratio <= 1.0:
        raise ValueError(f"topk ratio must be in (0, 1], got {ratio}")
    return "topk", ratio


def make_wire_format(spec: Optional[str],
                     chunk_elems: int = DEFAULT_CHUNK_ELEMS) -> WireFormat:
    """spec grammar: see :func:`parse_spec`."""
    scheme, ratio = parse_spec(spec)
    if ratio is None:
        return WireFormat(scheme, chunk_elems)
    return WireFormat(scheme, chunk_elems, topk_ratio=ratio)


# --------------------------------------------------------- chunk plumbing

def encode_chunk(x: jnp.ndarray, seq: int, start: int,
                 fmt: WireFormat) -> Chunk:
    """Encode one (n,) f32 window of the flat vector."""
    n = int(x.shape[0])
    return Chunk(seq=seq, start=start, length=n,
                 payload=fmt.codec.encode(x, fmt),
                 nbytes=fmt.chunk_wire_bytes(n))


def decode_chunk(chunk: Chunk, fmt: WireFormat) -> jnp.ndarray:
    """Decode one chunk back to its (length,) f32 window."""
    return fmt.codec.decode(chunk.payload, chunk.length, fmt)


@jax.jit
def _join_chunks(*vals):
    """One join of at most 16 decoded windows: the ``concatenate`` program
    eager ``jnp.concatenate`` runs, under a name of its own for profiles."""
    return jax.lax.concatenate(vals, 0)


def decode_concat(chunks: list[Chunk], fmt: WireFormat) -> jnp.ndarray:
    """Decode an in-order chunk sequence back to one flat f32 vector."""
    vals = [decode_chunk(c, fmt) for c in chunks if c.length]
    if not vals:
        return jnp.zeros((0,), jnp.float32)
    # jnp.concatenate's tree of at most 16-way joins, in its order; jit's
    # dispatch of each join is far cheaper on the host than eager's.
    while len(vals) > 1:
        groups = [vals[i:i + 16] for i in range(0, len(vals), 16)]
        vals = [_join_chunks(*g) if len(g) > 1 else g[0] for g in groups]
    return vals[0]


def encode_flat(vec: jnp.ndarray, fmt: WireFormat) -> list[Chunk]:
    """Split a flat (P,) vector into encoded wire chunks."""
    p = int(vec.shape[0])
    chunks, off, seq = [], 0, 0
    while off < p:
        n = min(fmt.chunk_elems, p - off)
        chunks.append(encode_chunk(jax.lax.slice(vec, (off,), (off + n,)),
                                   seq, off, fmt))
        off += n
        seq += 1
    if not chunks:             # zero-parameter model: one empty sentinel
        chunks.append(Chunk(0, 0, 0, jnp.zeros((0,), jnp.float32),
                            CHUNK_HEADER_BYTES))
    return chunks


def encode_flat_batch(vecs, fmt: WireFormat) -> list[list[Chunk]]:
    """Encode a stack of same-length flat vectors in one fused pass per
    chunk window.

    ``vecs`` is a (B, P) array or a list of B (P,) arrays.  Returns one
    chunk list per row, each *bit-identical* to ``encode_flat(vecs[i],
    fmt)`` (same windows, same per-row kernel math — see the per-codec
    ``encode_batch`` contract): batching collapses O(B x chunks) device
    dispatches into O(chunks), which is what makes coalescing one round's
    personalized resync re-encodes (runtime/dispatch.py ``encode_many``)
    a pure amortisation.
    """
    arr = vecs if hasattr(vecs, "ndim") and vecs.ndim == 2 \
        else jnp.stack(list(vecs))
    b, p = int(arr.shape[0]), int(arr.shape[1])
    codec = fmt.codec
    out: list[list[Chunk]] = [[] for _ in range(b)]
    off, seq = 0, 0
    while off < p:
        n = min(fmt.chunk_elems, p - off)
        window = jax.lax.slice(arr, (0, off), (b, off + n))
        payload = codec.encode_batch(window, fmt)
        nbytes = fmt.chunk_wire_bytes(n)
        for i in range(b):
            out[i].append(Chunk(seq=seq, start=off, length=n,
                                payload=codec.split_batch(payload, i),
                                nbytes=nbytes))
        off += n
        seq += 1
    if p == 0:                 # zero-parameter model: one empty sentinel
        for i in range(b):
            out[i].append(Chunk(0, 0, 0, jnp.zeros((0,), jnp.float32),
                                CHUNK_HEADER_BYTES))
    return out


def encode_error(vec: jnp.ndarray, chunks: list[Chunk],
                 fmt: WireFormat) -> Optional[jnp.ndarray]:
    """What the encoded wire failed to deliver: ``vec - decode(chunks)``.

    The per-payload error-feedback hook shared by both directions — the
    uplink folds it into the client's :class:`FlatErrorFeedback`, the
    downlink accumulates it into the server-side dispatch residual.
    Returns None for an empty vector (zero-parameter model).
    """
    if not int(vec.shape[0]):
        return None
    return vec - decode_concat(chunks, fmt)


class FlatErrorFeedback:
    """Per-client error feedback on the flat (P,) delta.

    The residual the lossy wire dropped last round is added to this round's
    delta before encoding, preserving convergence of compressed uploads
    (same contract as the per-leaf pytree ErrorFeedback it replaces — but
    one (P,) array instead of a delta-shaped pytree).
    """

    def __init__(self, residual: Optional[jnp.ndarray] = None):
        self.residual = residual

    def carry_in(self, delta: jnp.ndarray) -> jnp.ndarray:
        if self.residual is None:
            return delta
        return delta + self.residual

    def carry_out(self, sent: jnp.ndarray, decoded: jnp.ndarray) -> None:
        """sent = delta + old residual; decoded = what the wire delivered."""
        self.residual = sent - decoded
