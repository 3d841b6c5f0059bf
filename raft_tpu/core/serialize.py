"""Serialization — .npy-based array + header streaming for index save/load.

TPU-native counterpart of the reference's mdspan serializer
(core/serialize.hpp:35 ``serialize_mdspan``,
core/detail/mdspan_numpy_serializer.hpp): arrays stream as standard NumPy
``.npy`` records, scalars/POD headers as little-endian fixed-width fields.
Index checkpoint files produced here are self-describing and versioned
(cf. ``serialization_version`` in ivf_pq_types.hpp).
"""

from __future__ import annotations

import functools
import io
import json
import struct
from typing import Any, BinaryIO, Dict

import jax
import numpy as np

MAGIC = b"RAFTTPU\x00"


def serialize_scalar(f: BinaryIO, value) -> None:
    """Write one little-endian scalar (int64/float64/bool) with a type tag."""
    if isinstance(value, (bool, np.bool_)):
        f.write(b"b" + struct.pack("<?", bool(value)))
    elif isinstance(value, (int, np.integer)):
        f.write(b"i" + struct.pack("<q", int(value)))
    elif isinstance(value, (float, np.floating)):
        f.write(b"f" + struct.pack("<d", float(value)))
    elif isinstance(value, str):
        raw = value.encode("utf-8")
        f.write(b"s" + struct.pack("<q", len(raw)) + raw)
    else:
        raise TypeError(f"unsupported scalar type: {type(value)}")


def deserialize_scalar(f: BinaryIO):
    tag = f.read(1)
    if tag == b"b":
        return struct.unpack("<?", f.read(1))[0]
    if tag == b"i":
        return struct.unpack("<q", f.read(8))[0]
    if tag == b"f":
        return struct.unpack("<d", f.read(8))[0]
    if tag == b"s":
        (n,) = struct.unpack("<q", f.read(8))
        return f.read(n).decode("utf-8")
    raise ValueError(f"bad scalar tag: {tag!r}")


# Device↔host transfer granularity for big arrays: row slices bound the
# peak host and device memory a multi-GB transfer needs.
_FETCH_BYTES = 256 << 20


def _rows_per_chunk(arr, chunk_bytes: int = _FETCH_BYTES) -> int:
    return max(1, int(chunk_bytes
                      // max(arr.nbytes // max(arr.shape[0], 1), 1)))


def serialize_array(f: BinaryIO, arr) -> None:
    """Stream one array as a standard .npy record
    (reference: serialize_mdspan, core/serialize.hpp:35)."""
    if getattr(arr, "nbytes", 0) > _FETCH_BYTES and hasattr(arr, "shape") \
            and arr.ndim >= 1 and not isinstance(arr, np.ndarray):
        rows = _rows_per_chunk(arr)
        header = np.lib.format.header_data_from_array_1_0(
            np.empty((0,) + tuple(arr.shape[1:]),
                     np.dtype(str(arr.dtype))))
        header["shape"] = tuple(arr.shape)
        np.lib.format.write_array_header_1_0(f, header)
        for a in range(0, arr.shape[0], rows):
            block = np.asarray(jax.device_get(arr[a:a + rows]))
            f.write(np.ascontiguousarray(block).tobytes())
        return
    np.save(f, np.asarray(jax.device_get(arr)), allow_pickle=False)


def deserialize_array(f: BinaryIO) -> np.ndarray:
    return np.load(f, allow_pickle=False)


@functools.lru_cache(maxsize=None)
def _chunk_writer(ndim: int):
    import jax
    import jax.numpy as jnp

    def upd(b, blk, i):
        idx = (i,) + (jnp.int32(0),) * (ndim - 1)
        return jax.lax.dynamic_update_slice(b, blk, idx)

    return jax.jit(upd, donate_argnums=0)


def to_device_chunked(a: np.ndarray, chunk_bytes: int = _FETCH_BYTES):
    """Host→device transfer in row slices into a donated buffer — the
    upload mirror of serialize_array's sliced fetches (~256 MB slices
    bound peak device allocation at buffer + one slice)."""
    import jax.numpy as jnp

    if a.nbytes <= chunk_bytes:
        return jnp.asarray(a)
    rows = _rows_per_chunk(a, chunk_bytes)
    buf = jnp.zeros(a.shape, a.dtype)
    upd = _chunk_writer(a.ndim)
    for i in range(0, a.shape[0], rows):
        if i + rows > a.shape[0] and i > 0:
            # ragged tail: overlap-write the LAST full-width slice so
            # every chunk compiles to one shape
            i = a.shape[0] - rows
        blk = np.ascontiguousarray(a[i:i + rows])
        buf = upd(buf, jnp.asarray(blk), jnp.int32(i))
    return buf


def serialize_header(f: BinaryIO, kind: str, version: int, meta: Dict[str, Any]) -> None:
    """Write the container header: magic, kind, version, JSON metadata."""
    f.write(MAGIC)
    serialize_scalar(f, kind)
    serialize_scalar(f, version)
    serialize_scalar(f, json.dumps(meta, sort_keys=True))


def deserialize_header(f: BinaryIO, expected_kind: str):
    magic = f.read(len(MAGIC))
    if magic != MAGIC:
        raise ValueError("not a raft_tpu serialized file (bad magic)")
    kind = deserialize_scalar(f)
    if kind != expected_kind:
        raise ValueError(f"expected {expected_kind!r} file, got {kind!r}")
    version = deserialize_scalar(f)
    meta = json.loads(deserialize_scalar(f))
    return version, meta


def save_arrays(path: str, kind: str, version: int, meta: Dict[str, Any], arrays: Dict[str, Any]) -> None:
    """Save a named-array container (one file per index)."""
    with open(path, "wb") as f:
        serialize_header(f, kind, version, meta)
        serialize_scalar(f, len(arrays))
        for name, arr in arrays.items():
            serialize_scalar(f, name)
            serialize_array(f, arr)


def load_arrays(path: str, kind: str):
    """Load a named-array container → (version, meta, {name: np.ndarray})."""
    with open(path, "rb") as f:
        version, meta = deserialize_header(f, kind)
        n = deserialize_scalar(f)
        arrays = {}
        for _ in range(n):
            name = deserialize_scalar(f)
            arrays[name] = deserialize_array(f)
    return version, meta, arrays
