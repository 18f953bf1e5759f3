"""Byte-accurate codecs for the flat-buffer sparse sync payloads.

A *payload* is what ``core.sparsify.pack_phi`` produces for one hop of the
every-H consensus: ``(values [k] f32, indices [k] int32)`` over a flat
vector of ``size`` entries (φ=0 degenerates to the dense vector). Each codec
defines an exact wire format and three mutually consistent views of it:

  * ``encode``            -> the byte stream itself (numpy ``uint8``)
  * ``decode``            -> the payload the receiver reconstructs,
                             bit-exact against ``encode``'s output
  * ``measure_bits``      -> closed-form stream length; ALWAYS equals
                             ``8 * len(encode(...))``
  * ``measure_bits_jax``  -> the same count as a traced jnp scalar, so the
                             simulator can account bits inside jitted code
                             without materializing byte streams

Registered codecs (``get_codec``):

  ``dense-f32``        raw little-endian f32 of the dense vector — exactly
                       the paper's analytic accounting at φ=0
                       (``LatencyParams.payload(0.0) == 32·Q``).
  ``dense-bf16``       dense vector in bfloat16 (16·Q bits).
  ``bitmap``           Q-bit presence bitmap (LSB-first bytes) + values of
                       the set bits in index order. Alias ``bitmap+values``.
  ``delta-varint``     sorted index gaps as LEB128 varints + values.
  ``delta-gamma``      sorted index gaps (+1) as MSB-first Elias-gamma
                       codes + values. Alias ``delta-elias-gamma``.
  ``*-q8``             bitmap/delta variants with 8-bit linearly quantized
                       values (scale = max|v|/127, carried as an f32
                       header); the quantization error is fed back through
                       the sync's ``eps``/``e`` buffers when
                       ``HFLConfig.wire_format="q8"`` (see ``core.hfl``).
  ``best``             meta-codec: per payload, the cheapest registered
                       concrete codec + a 1-byte codec-id header. Bitmap
                       wins at low φ (dense-ish index sets), the delta
                       streams at high φ; ``choose`` reports the winner so
                       benchmarks can locate the crossover.

Codecs canonicalize payloads by sorting on index (scatter-add semantics are
order-invariant, so this is lossless); the bitmap codec additionally
coalesces duplicate indices by summation (a bitmap cannot represent
multiplicity). ``decode(encode(p))`` is bit-exact for f32 codecs and equals
``wire_values(p)`` (the receiver-visible rounding) for bf16/q8.

Traced bit counts are int32 (jax's default-x64-off integer): the static
components (``jnp.int32`` of a Python int) raise on overflow at trace
time, but traced SUMS wrap silently like any XLA integer op — the counts
are exact only for payloads up to ~50M transmitted entries (~2^31/40 at
delta-varint's worst case). That is far beyond anything the CPU-side
probe measures; the host ``measure_bits`` path (Python ints) is exact at
any scale and is what the benchmarks use.
"""
from __future__ import annotations

import struct
from typing import Dict, Tuple

import jax.numpy as jnp
import ml_dtypes
import numpy as np

# ---------------------------------------------------------------------------
# Bit-stream helpers (MSB-first, used by the Elias-gamma index stream)
# ---------------------------------------------------------------------------


class BitWriter:
    """MSB-first bit packer; ``flush`` zero-pads to a byte boundary."""

    def __init__(self):
        self._out = bytearray()
        self._cur = 0
        self._n = 0

    def write(self, value: int, nbits: int) -> None:
        for b in range(nbits - 1, -1, -1):
            self._cur = (self._cur << 1) | ((value >> b) & 1)
            self._n += 1
            if self._n == 8:
                self._out.append(self._cur)
                self._cur = 0
                self._n = 0

    def flush(self) -> bytes:
        if self._n:
            self._out.append(self._cur << (8 - self._n))
            self._cur = 0
            self._n = 0
        return bytes(self._out)


class BitReader:
    def __init__(self, buf):
        self._buf = buf
        self._pos = 0  # bit cursor

    def read(self, nbits: int) -> int:
        out = 0
        for _ in range(nbits):
            byte = self._buf[self._pos >> 3]
            out = (out << 1) | ((byte >> (7 - (self._pos & 7))) & 1)
            self._pos += 1
        return out

    def read_unary_zeros(self) -> int:
        n = 0
        while self.read(1) == 0:
            n += 1
        return n


def elias_gamma_bits(n) -> int:
    """Bit length of the Elias-gamma code of ``n >= 1``: 2·⌊log2 n⌋ + 1."""
    return 2 * (int(n).bit_length() - 1) + 1


def varint_len(d) -> int:
    """LEB128 byte length of ``d >= 0``."""
    d = int(d)
    return max(1, -(-d.bit_length() // 7))


# ---------------------------------------------------------------------------
# Value formats: how the k transmitted values ride the wire
# ---------------------------------------------------------------------------


class _F32Values:
    """Raw little-endian float32; lossless."""

    bits, header_bits, tag = 32, 0, "f32"

    def encode(self, v: np.ndarray) -> bytes:
        return v.astype("<f4").tobytes()

    def parse(self, buf: bytes, off: int, k: int) -> Tuple[np.ndarray, int]:
        v = np.frombuffer(buf, dtype="<f4", count=k, offset=off)
        return v.astype(np.float32), off + 4 * k

    def wire(self, v: np.ndarray) -> np.ndarray:
        return v.astype(np.float32)

    def nbits(self, k: int) -> int:
        return 32 * k

    def nbits_jax(self, values):
        return jnp.int32(32 * values.shape[0])


class _BF16Values:
    """bfloat16 round-to-nearest-even — the wire format of the engine's
    ``quantized_sparse`` mode (``core.hfl._wire_round``)."""

    bits, header_bits, tag = 16, 0, "bf16"

    def encode(self, v: np.ndarray) -> bytes:
        return v.astype(np.float32).astype(ml_dtypes.bfloat16).tobytes()

    def parse(self, buf: bytes, off: int, k: int) -> Tuple[np.ndarray, int]:
        v = np.frombuffer(buf, dtype=ml_dtypes.bfloat16, count=k, offset=off)
        return v.astype(np.float32), off + 2 * k

    def wire(self, v: np.ndarray) -> np.ndarray:
        return v.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)

    def nbits(self, k: int) -> int:
        return 16 * k

    def nbits_jax(self, values):
        return jnp.int32(16 * values.shape[0])


class _Q8Values:
    """8-bit linear quantization: codes = clip(rint(v/scale), ±127) with
    scale = max|v|/127 carried as an f32 header. All arithmetic is f32 so
    the host round-trip is bit-identical to the traced
    ``core.hfl._wire_round(x, "q8")``."""

    bits, header_bits, tag = 8, 32, "q8"

    @staticmethod
    def scale_of(v: np.ndarray) -> np.float32:
        amax = np.float32(np.max(np.abs(v))) if v.size else np.float32(0.0)
        return amax / np.float32(127.0) if amax > 0 else np.float32(1.0)

    def encode(self, v: np.ndarray) -> bytes:
        v = v.astype(np.float32)
        scale = self.scale_of(v)
        codes = np.clip(np.rint(v / scale), -127, 127).astype(np.int8)
        return struct.pack("<f", scale) + codes.tobytes()

    def parse(self, buf: bytes, off: int, k: int) -> Tuple[np.ndarray, int]:
        (scale,) = struct.unpack_from("<f", buf, off)
        codes = np.frombuffer(buf, dtype=np.int8, count=k, offset=off + 4)
        return codes.astype(np.float32) * np.float32(scale), off + 4 + k

    def wire(self, v: np.ndarray) -> np.ndarray:
        v = v.astype(np.float32)
        scale = self.scale_of(v)
        codes = np.clip(np.rint(v / scale), -127, 127).astype(np.float32)
        return codes * scale

    def nbits(self, k: int) -> int:
        return 32 + 8 * k

    def nbits_jax(self, values):
        return jnp.int32(32 + 8 * values.shape[0])


_VALUE_FORMATS = {"f32": _F32Values(), "bf16": _BF16Values(), "q8": _Q8Values()}


# ---------------------------------------------------------------------------
# Codec base
# ---------------------------------------------------------------------------


def _canonical(values, indices) -> Tuple[np.ndarray, np.ndarray]:
    """Sort a payload by index (stable; scatter-add is order-invariant)."""
    v = np.asarray(values, np.float32).reshape(-1)
    i = np.asarray(indices).reshape(-1).astype(np.int64)
    order = np.argsort(i, kind="stable")
    return v[order], i[order]


class Codec:
    """Interface; see module docstring for the invariants."""

    name: str = ""
    aliases: Tuple[str, ...] = ()

    @property
    def value_format(self) -> str:
        """Fidelity of the value stream: f32 | bf16 | q8 | mixed (best).
        The engine warns when this disagrees with the sync's simulated
        wire rounding (``HFLConfig.wire_format``)."""
        fmt = getattr(self, "_fmt", None)
        return fmt.tag if fmt is not None else "mixed"

    def encode(self, values, indices, size: int) -> np.ndarray:
        raise NotImplementedError

    def decode(self, blob, size: int) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def measure_bits(self, values, indices, size: int) -> int:
        raise NotImplementedError

    def measure_bits_jax(self, values, indices, size: int):
        raise NotImplementedError

    def wire_values(self, values) -> np.ndarray:
        """Receiver-visible values (identity for f32, rounded for bf16/q8)."""
        raise NotImplementedError

    def decode_dense(self, blob, size: int) -> np.ndarray:
        """Scatter-add view of ``decode`` (the consensus-side reconstruction)."""
        v, i = self.decode(blob, size)
        out = np.zeros(size, np.float32)
        np.add.at(out, i, v)
        return out


class DenseCodec(Codec):
    """The whole dense vector on the wire; the φ=0 reference formats."""

    def __init__(self, name: str, fmt: str):
        self.name = name
        self._fmt = _VALUE_FORMATS[fmt]

    def _densify(self, values, indices, size: int) -> np.ndarray:
        v, i = _canonical(values, indices)
        out = np.zeros(size, np.float32)
        np.add.at(out, i, v)
        return out

    def encode(self, values, indices, size: int) -> np.ndarray:
        dense = self._densify(values, indices, size)
        stream = self._fmt.encode(dense)
        return np.frombuffer(stream, np.uint8)

    def decode(self, blob, size: int):
        buf = np.asarray(blob, np.uint8).tobytes()
        v, _ = self._fmt.parse(buf, 0, size)
        return v, np.arange(size, dtype=np.int32)

    def measure_bits(self, values, indices, size: int) -> int:
        return self._fmt.bits * size

    def measure_bits_jax(self, values, indices, size: int):
        return jnp.int32(self._fmt.bits * size)

    def wire_values(self, values):
        return self._fmt.wire(np.asarray(values, np.float32))


class BitmapCodec(Codec):
    """``ceil(size/8)`` bitmap bytes (LSB-first) + set-bit values in index
    order. Duplicate indices are coalesced by summation. The bit-pack has a
    Pallas kernel path (``repro.kernels.bitpack``; compiled on a TPU,
    interpreted on the CPU) selectable with ``impl="pallas"``; both paths
    emit identical bytes."""

    def __init__(self, name: str, fmt: str, aliases: Tuple[str, ...] = ()):
        self.name = name
        self.aliases = aliases
        self._fmt = _VALUE_FORMATS[fmt]

    def _coalesce(self, values, indices):
        v, i = _canonical(values, indices)
        if v.size == 0:
            return v, i.astype(np.int64)
        firsts = np.ones(i.size, bool)
        firsts[1:] = i[1:] != i[:-1]
        starts = np.nonzero(firsts)[0]
        return np.add.reduceat(v, starts).astype(np.float32), i[starts]

    def encode(self, values, indices, size: int, *, impl: str = "np") -> np.ndarray:
        v, i = self._coalesce(values, indices)
        if impl == "np":
            bits = np.zeros(size, np.uint8)
            bits[i] = 1
            packed = np.packbits(bits, bitorder="little").tobytes()
        elif impl == "pallas":
            from repro.kernels.bitpack import ops as _bp

            mask = jnp.zeros((size,), jnp.float32).at[jnp.asarray(i)].set(1.0)
            packed = _bp.bitpack_bytes(mask)
        else:
            raise ValueError(impl)
        stream = packed + self._fmt.encode(v)
        return np.frombuffer(stream, np.uint8)

    def decode(self, blob, size: int):
        buf = np.asarray(blob, np.uint8).tobytes()
        nb = (size + 7) // 8
        bits = np.unpackbits(
            np.frombuffer(buf, np.uint8, count=nb), bitorder="little"
        )[:size]
        idx = np.nonzero(bits)[0].astype(np.int32)
        v, _ = self._fmt.parse(buf, nb, len(idx))
        return v, idx

    def measure_bits(self, values, indices, size: int) -> int:
        i = np.asarray(indices).reshape(-1)
        k_uniq = int(np.unique(i).size)
        return 8 * ((size + 7) // 8) + self._fmt.header_bits + self._fmt.bits * k_uniq

    def measure_bits_jax(self, values, indices, size: int):
        idx = jnp.sort(jnp.asarray(indices).reshape(-1))
        if idx.shape[0] == 0:
            k_uniq = jnp.int32(0)
        else:
            k_uniq = 1 + jnp.sum((idx[1:] != idx[:-1]).astype(jnp.int32))
        return (
            jnp.int32(8 * ((size + 7) // 8) + self._fmt.header_bits)
            + jnp.int32(self._fmt.bits) * k_uniq
        )

    def wire_values(self, values):
        return self._fmt.wire(np.asarray(values, np.float32))


class DeltaCodec(Codec):
    """``[uint32 k][value header][index-gap stream][values]``. Gaps are
    deltas of the sorted indices (first gap = the first index); ``varint``
    emits them as LEB128 bytes, ``gamma`` as MSB-first Elias-gamma codes of
    ``gap+1`` (gamma cannot code 0) padded to a byte boundary."""

    def __init__(self, name: str, scheme: str, fmt: str,
                 aliases: Tuple[str, ...] = ()):
        assert scheme in ("varint", "gamma")
        self.name = name
        self.aliases = aliases
        self._scheme = scheme
        self._fmt = _VALUE_FORMATS[fmt]

    @staticmethod
    def _gaps(i: np.ndarray) -> np.ndarray:
        d = np.empty(i.size, np.int64)
        if i.size:
            d[0] = i[0]
            d[1:] = i[1:] - i[:-1]
        return d

    def encode(self, values, indices, size: int) -> np.ndarray:
        v, i = _canonical(values, indices)
        out = bytearray(struct.pack("<I", v.size))
        if self._scheme == "varint":
            for d in self._gaps(i):
                d = int(d)
                while True:
                    byte = d & 0x7F
                    d >>= 7
                    out.append(byte | (0x80 if d else 0))
                    if not d:
                        break
        else:
            bw = BitWriter()
            for d in self._gaps(i):
                n = int(d) + 1
                zlen = n.bit_length() - 1
                bw.write(0, zlen)
                bw.write(n, zlen + 1)
            out += bw.flush()
        out += self._fmt.encode(v)
        return np.frombuffer(bytes(out), np.uint8)

    def decode(self, blob, size: int):
        buf = np.asarray(blob, np.uint8).tobytes()
        (k,) = struct.unpack_from("<I", buf, 0)
        off = 4
        gaps = np.empty(k, np.int64)
        if self._scheme == "varint":
            for j in range(k):
                d, shift = 0, 0
                while True:
                    byte = buf[off]
                    off += 1
                    d |= (byte & 0x7F) << shift
                    shift += 7
                    if not byte & 0x80:
                        break
                gaps[j] = d
        else:
            br = BitReader(buf[off:])
            nbits = 0
            for j in range(k):
                z = br.read_unary_zeros()
                n = (1 << z) | br.read(z) if z else 1
                gaps[j] = n - 1
                nbits += 2 * z + 1
            off += (nbits + 7) // 8
        idx = np.cumsum(gaps).astype(np.int32) if k else np.zeros(0, np.int32)
        v, _ = self._fmt.parse(buf, off, k)
        return v, idx

    def measure_bits(self, values, indices, size: int) -> int:
        _, i = _canonical(values, indices)
        d = self._gaps(i)
        if self._scheme == "varint":
            idx_bits = 8 * sum(varint_len(g) for g in d)
        else:
            gb = sum(elias_gamma_bits(int(g) + 1) for g in d)
            idx_bits = 8 * ((gb + 7) // 8)
        return 32 + self._fmt.header_bits + idx_bits + self._fmt.bits * i.size

    def measure_bits_jax(self, values, indices, size: int):
        idx = jnp.sort(jnp.asarray(indices).reshape(-1).astype(jnp.int32))
        k = idx.shape[0]
        if k == 0:
            idx_bits = jnp.int32(0)
        else:
            d = jnp.concatenate([idx[:1], idx[1:] - idx[:-1]])
            if self._scheme == "varint":
                nb = jnp.ones_like(d)
                for j in (7, 14, 21, 28):
                    nb = nb + (d >= (1 << j)).astype(jnp.int32)
                idx_bits = 8 * jnp.sum(nb)
            else:
                m = d + 1
                fl = jnp.zeros_like(m)
                for j in range(1, 31):  # int32 gaps: m < 2^31
                    fl = fl + (m >= (1 << j)).astype(jnp.int32)
                gb = jnp.sum(2 * fl + 1)
                idx_bits = 8 * ((gb + 7) // 8)
        return (
            jnp.int32(32 + self._fmt.header_bits)
            + idx_bits
            + jnp.int32(self._fmt.bits * k)
        )

    def wire_values(self, values):
        return self._fmt.wire(np.asarray(values, np.float32))


class BestCodec(Codec):
    """Meta-codec: the cheapest concrete codec per payload, selected by the
    closed-form ``measure_bits`` (which equals the stream length by the
    codec invariant) with a 1-byte codec-id header. First-in-order wins
    ties, so the choice is deterministic."""

    name = "best"

    def __init__(self, candidates):
        self._cands = tuple(candidates)

    def choose(self, values, indices, size: int):
        """-> (winning codec, its stream bits, without the id header)."""
        bits = [c.measure_bits(values, indices, size) for c in self._cands]
        j = int(np.argmin(bits))
        return self._cands[j], bits[j]

    def encode(self, values, indices, size: int) -> np.ndarray:
        codec, _ = self.choose(values, indices, size)
        cid = self._cands.index(codec)
        sub = codec.encode(values, indices, size)
        return np.concatenate([np.array([cid], np.uint8), sub])

    def decode(self, blob, size: int):
        blob = np.asarray(blob, np.uint8)
        return self._cands[int(blob[0])].decode(blob[1:], size)

    def measure_bits(self, values, indices, size: int) -> int:
        return 8 + self.choose(values, indices, size)[1]

    def measure_bits_jax(self, values, indices, size: int):
        return 8 + jnp.min(
            jnp.stack(
                [c.measure_bits_jax(values, indices, size) for c in self._cands]
            )
        )

    def wire_values(self, values):
        # id-independent only for f32 candidates; the winner's rounding is
        # what the receiver sees. Report the f32 identity (the winner may
        # round further; use the concrete codec for exact wire semantics).
        return np.asarray(values, np.float32)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

CODECS: Dict[str, Codec] = {}
_ALIASES: Dict[str, str] = {}


def _register(codec: Codec) -> Codec:
    CODECS[codec.name] = codec
    for a in codec.aliases:
        _ALIASES[a] = codec.name
    return codec


_register(DenseCodec("dense-f32", "f32"))
_register(DenseCodec("dense-bf16", "bf16"))
_register(BitmapCodec("bitmap", "f32", aliases=("bitmap+values",)))
_register(BitmapCodec("bitmap-q8", "q8"))
_register(DeltaCodec("delta-varint", "varint", "f32"))
_register(DeltaCodec("delta-varint-q8", "varint", "q8"))
_register(DeltaCodec("delta-gamma", "gamma", "f32",
                     aliases=("delta-elias-gamma",)))
_register(DeltaCodec("delta-gamma-q8", "gamma", "q8"))
_register(BestCodec([CODECS[n] for n in (
    "dense-f32", "dense-bf16", "bitmap", "bitmap-q8",
    "delta-varint", "delta-varint-q8", "delta-gamma", "delta-gamma-q8",
)]))


def get_codec(name: str) -> Codec:
    key = _ALIASES.get(name, name)
    if key not in CODECS:
        raise KeyError(
            f"unknown codec {name!r}; choose from {sorted(list_codecs())}"
        )
    return CODECS[key]


def list_codecs():
    return tuple(CODECS) + tuple(_ALIASES)
