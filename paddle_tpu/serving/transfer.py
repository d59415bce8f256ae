"""KV-block transfer wire: serialize pool blocks for P/D disaggregation.

The serving fleet's prefill/decode split ships FINISHED KV blocks from
a prefill replica's pool into a decode replica's pool. This module owns
that wire: one payload is an ordered CHAIN of (content digest, block
rows) pairs sliced out of the head-major pool (k/v ``[L, Hkv, M, Dh]``;
int8/int4 pools add the ``[L, Hkv, M]`` fp32 scale tables — scales
travel WITH their block, the write-local property that makes blocks
relocatable across pools), stamped with the pool layout / kv_dtype /
per-block slab shape so a mismatched receiver refuses loudly instead of
adopting garbage.

Deserialize + write is the receiving side's half: the decode engine
allocates local blocks, writes the payload rows in (functional jnp
updates at block-aligned offsets — ``write_block``), and publishes the
digests through the ordinary prefix-cache publish path
(``PagedDecodeEngine.import_prefix``). Adoption is then a plain prefix
cache hit, so generation downstream is bitwise the colocated run
(the PR-6 hit-vs-cold guarantee).

The wire is explicit binary, not pickle: a fixed magic + version, a
JSON header naming layout/kv_dtype/digests/array specs, then the raw
C-order buffers in documented order. Everything roundtrips BITWISE for
fp32, bf16, int8 and int4 pools (tests/test_fleet.py). Serialization
host-copies only the shipped block slabs (``np.asarray`` per slab, not
per pool leaf); jax is only touched in ``write_block``/``write_blocks``.
"""

import json
import struct
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

MAGIC = b"PTKV"
VERSION = 1

# THE description of the engine's pool pytree, which every model's pool
# satisfies (per-head K/V, their int8 / int4 storage with scale tables,
# a model that pages only some layers and keeps recurrent rows per
# slot, a latent pool): a leaf named here is a PAGE TABLE
# ``[layers, groups, M, ...]`` whose axis 2 is the flat position axis
# (block i owns rows [i * block_size, (i + 1) * block_size)); a page is
# that span of every table the pool has, which is what is copied to
# export, adopt, spill or snapshot a block, in this order; what one
# resident token costs is one row of every table
# (``bytes_per_token``). Any OTHER leaf is state per engine slot
# (``slot_state_bytes``): a pool that has some cannot resume from its
# pages alone.
ARRAY_ORDER = ("k", "v", "k_scale", "v_scale", "latent")


def bytes_per_token(cache) -> int:
    """HBM bytes one resident token costs: a row of every page table."""
    return sum(int(np.prod(cache[n].shape)) // cache[n].shape[2]
               * np.dtype(cache[n].dtype).itemsize
               for n in ARRAY_ORDER if n in cache)


def slot_state_bytes(cache) -> int:
    """Bytes of the leaves that are no page table: rows per slot."""
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for n, a in cache.items() if n not in ARRAY_ORDER)


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including the ml_dtypes extras (bfloat16)
    a jax pool may store."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _block_slab(leaf: np.ndarray, block: int, block_size: int):
    """One block's rows out of a pool leaf: the position axis is axis 2
    for the 4D value arrays ([L, Hkv, M, Dh]) and the trailing axis for
    the 3D scale tables ([L, Hkv, M])."""
    s = block * block_size
    if leaf.ndim == 4:
        return leaf[:, :, s:s + block_size, :]
    return leaf[:, :, s:s + block_size]


def pool_meta(cache, block_size: int, kv_dtype: str = "none") -> dict:
    """The stamp a payload carries (and ``check_pool_match`` verifies):
    pool layout, KV storage width, block size, and each array's
    per-block slab shape + dtype."""
    from paddle_tpu.models.transformer import POOL_LAYOUT
    arrays = {}
    for name in ARRAY_ORDER:
        if name not in cache:
            continue
        leaf = cache[name]
        shape = list(leaf.shape)
        shape[2] = int(block_size)
        arrays[name] = {"shape": shape, "dtype": str(leaf.dtype)}
    return {"layout": POOL_LAYOUT, "kv_dtype": str(kv_dtype or "none"),
            "block_size": int(block_size), "arrays": arrays}


def serialize_raw_blocks(meta: dict,
                         items: Sequence[Tuple[bytes, Dict[str, np.ndarray]]],
                         trace: Optional[str] = None) -> bytes:
    """Pack already-materialized ``(digest, {name: slab})`` pairs under
    a prebuilt :func:`pool_meta` stamp. This is :func:`serialize_blocks`
    with the pool-slicing step factored out, so a sender can mix slabs
    read from its HBM pool with slabs round-tripped through a spill
    tier (``serving/tiers.py``) in ONE chain-ordered payload — the
    receiving side cannot tell the difference, which is the point."""
    meta = dict(meta)
    meta["digests"] = [bytes(d).hex() for d, _ in items]
    if trace:
        meta["trace"] = str(trace)
    names = [n for n in ARRAY_ORDER if n in meta["arrays"]]
    header = json.dumps(meta).encode("utf-8")
    out = [MAGIC, struct.pack("<II", VERSION, len(header)), header]
    for _, arrays in items:
        for n in names:
            out.append(np.ascontiguousarray(
                np.asarray(arrays[n])).tobytes())
    return b"".join(out)


def serialize_blocks(cache, block_ids: Sequence[int],
                     digests: Sequence[bytes], block_size: int,
                     kv_dtype: str = "none",
                     trace: Optional[str] = None) -> bytes:
    """Pack ``block_ids``'s pool rows (chain order, one digest per
    block) into one stamped payload. ``trace`` rides in the header so
    the fleet trace context survives the P/D hop INSIDE the payload —
    the importing replica emits its adoption event on the same track
    even when the payload is relayed through a router that did not
    stamp the wire op."""
    if len(block_ids) != len(digests):
        raise ValueError(f"{len(block_ids)} blocks vs "
                         f"{len(digests)} digests")
    meta = pool_meta(cache, block_size, kv_dtype)
    meta["digests"] = [bytes(d).hex() for d in digests]
    if trace:
        meta["trace"] = str(trace)
    names = [n for n in ARRAY_ORDER if n in meta["arrays"]]
    header = json.dumps(meta).encode("utf-8")
    out = [MAGIC, struct.pack("<II", VERSION, len(header)), header]
    # slice each block's slab FIRST, then host-copy only the slab — a
    # device pool ships B*block_size rows over the wire, not the whole
    # pool per export
    for b in block_ids:
        for n in names:
            out.append(np.ascontiguousarray(np.asarray(
                _block_slab(cache[n], int(b), block_size))).tobytes())
    return b"".join(out)


def deserialize_blocks(payload: bytes
                       ) -> Tuple[dict, List[Tuple[bytes, Dict[str, np.ndarray]]]]:
    """Unpack a payload into its stamp + the ordered
    ``(digest, {array name: block slab})`` chain."""
    if payload[:4] != MAGIC:
        raise ValueError("not a KV transfer payload (bad magic)")
    version, hlen = struct.unpack_from("<II", payload, 4)
    if version != VERSION:
        raise ValueError(f"KV payload version {version}, expected "
                         f"{VERSION}")
    meta = json.loads(payload[12:12 + hlen].decode("utf-8"))
    names = [n for n in ARRAY_ORDER if n in meta["arrays"]]
    specs = [(n, tuple(meta["arrays"][n]["shape"]),
              _np_dtype(meta["arrays"][n]["dtype"])) for n in names]
    off = 12 + hlen
    blocks = []
    for hexd in meta["digests"]:
        arrays = {}
        for n, shape, dt in specs:
            nbytes = int(np.prod(shape)) * dt.itemsize
            arrays[n] = np.frombuffer(
                payload, dtype=dt, count=int(np.prod(shape)),
                offset=off).reshape(shape)
            off += nbytes
        blocks.append((bytes.fromhex(hexd), arrays))
    if off != len(payload):
        raise ValueError(f"KV payload size mismatch: consumed {off} of "
                         f"{len(payload)} bytes")
    return meta, blocks


def check_pool_match(meta: dict, cache, block_size: int,
                     kv_dtype: str = "none"):
    """Refuse a payload whose stamp does not match the receiving pool —
    adopting bytes across a layout / storage-width / geometry mismatch
    would poison the prefix cache silently."""
    want = pool_meta(cache, block_size, kv_dtype)
    for key in ("layout", "kv_dtype", "block_size", "arrays"):
        if meta.get(key) != want[key]:
            raise ValueError(
                f"KV payload {key} mismatch: payload "
                f"{meta.get(key)!r} vs pool {want[key]!r}")


def write_block(cache, block: int, arrays: Dict[str, np.ndarray],
                block_size: int):
    """Write one deserialized block slab into ``cache`` at ``block``
    (functional update; returns the new pytree). Dtypes already match
    by ``check_pool_match``, so the copy is bitwise."""
    return write_blocks(cache, [(block, arrays)], block_size)


def write_blocks(cache, writes: Sequence[Tuple[int, Dict[str, np.ndarray]]],
                 block_size: int):
    """Batched :func:`write_block`: ONE functional scatter per pool
    leaf for the whole chain (per-block ``.at[].set`` would copy the
    full pool once per adopted block)."""
    if not writes:
        return cache
    import jax.numpy as jnp
    bs = int(block_size)
    idx = jnp.asarray(np.concatenate(
        [np.arange(int(b) * bs, int(b) * bs + bs) for b, _ in writes]))
    out = dict(cache)
    for name in writes[0][1]:
        slab = jnp.asarray(np.concatenate(
            [np.asarray(arrays[name]) for _, arrays in writes], axis=2))
        leaf = out[name]
        if leaf.ndim == 4:
            out[name] = leaf.at[:, :, idx, :].set(slab)
        else:
            out[name] = leaf.at[:, :, idx].set(slab)
    return out
