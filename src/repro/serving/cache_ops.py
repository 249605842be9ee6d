"""Structure-aware batch-axis ops over model cache pytrees.

Caches built by models.model.make_caches have family-specific layouts
(layer-stacked KV, MLA latent, SSM state, hybrid group caches); these helpers
slice/insert per-request rows for continuous batching and serialize per-token
blocks for the EMS context cache.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.attention import KVCache
from repro.models.mamba2 import SSMState
from repro.models.model import build_plan, make_caches
from repro.models.model import cache_batch_axes as _model_cache_batch_axes


def cache_batch_axes(cfg: ModelConfig, caches: Dict[str, Any]) -> Dict[str, Any]:
    """Pytree of batch-axis indices matching the cache structure
    (None = unbatched leaf, e.g. length scalars). The structure is derived
    from cfg alone; ``caches`` is accepted for call-site symmetry."""
    del caches
    return _model_cache_batch_axes(cfg)


def _map2(fn, tree, axes):
    return jax.tree.map(fn, tree, axes)


def slice_request(cfg: ModelConfig, caches, row: int):
    """Extract one request's cache (batch dim kept = 1)."""
    axes = cache_batch_axes(cfg, caches)
    return _map2(
        lambda leaf, ax: leaf if ax is None else
        jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=ax),
        caches, axes)


def insert_request(cfg: ModelConfig, caches, req_cache, row: int):
    """Write one request's cache (batch=1) into batch slot ``row``."""
    axes = cache_batch_axes(cfg, caches)
    return jax.tree.map(
        lambda dst, src, ax: dst if ax is None else
        jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), row, axis=ax),
        caches, req_cache, axes)


def seq_slice(cfg: ModelConfig, caches, start: int, length: int):
    """Slice ``length`` tokens of sequence state (KV/MLA buffers only) —
    the payload unit of context caching. SSM states are not sliceable by
    token (noted inapplicability, DESIGN.md §3)."""
    out = {}
    for seg in build_plan(cfg):
        c = caches[seg.name]
        if seg.kind in ("dense", "moe"):
            if cfg.attention_kind == "mla":
                out[seg.name] = jax.lax.dynamic_slice_in_dim(
                    c["mla"], start, length, axis=2)
            else:
                out[seg.name] = (
                    jax.lax.dynamic_slice_in_dim(c.k, start, length, axis=2),
                    jax.lax.dynamic_slice_in_dim(c.v, start, length, axis=2))
    return out


def seq_insert(cfg: ModelConfig, caches, payload: Dict[str, Any], start: int):
    """Insert a seq_slice payload back at token offset ``start``."""
    new = dict(caches)
    for seg in build_plan(cfg):
        if seg.name not in payload:
            continue
        c = caches[seg.name]
        pl = payload[seg.name]
        if cfg.attention_kind == "mla":
            new[seg.name] = {**c, "mla": jax.lax.dynamic_update_slice_in_dim(
                c["mla"], pl.astype(c["mla"].dtype), start, axis=2)}
        else:
            k, v = pl
            new[seg.name] = KVCache(
                jax.lax.dynamic_update_slice_in_dim(c.k, k.astype(c.k.dtype), start, axis=2),
                jax.lax.dynamic_update_slice_in_dim(c.v, v.astype(c.v.dtype), start, axis=2),
                c.length)
    return new


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _pack_blocks(cfg: ModelConfig, caches, n_blocks: int, block: int) -> jax.Array:
    """Jitted batched EMS pack: all block payloads in one slice+pack."""
    payload = seq_slice(cfg, caches, 0, n_blocks * block)
    rows = []
    for leaf in jax.tree.leaves(payload):
        # leaf: (L, B, n_blocks*block, ...) — bring the block index to the
        # front so row ``bi`` ravels exactly like
        # ``pack_payload(seq_slice(cfg, caches, bi*block, block))``.
        l, b = leaf.shape[0], leaf.shape[1]
        x = leaf.reshape((l, b, n_blocks, block) + leaf.shape[3:])
        x = jnp.moveaxis(x, 2, 0).astype(jnp.float32).reshape(n_blocks, -1)
        rows.append(x)
    return jnp.concatenate(rows, axis=1)


def pack_blocks(cfg: ModelConfig, caches, n_blocks: int,
                block: int) -> List[np.ndarray]:
    """Build every EMS block payload for tokens [0, n_blocks*block) in ONE
    jitted slice+pack instead of a Python ``seq_slice``/``pack_payload``
    round-trip per block. Row ``bi`` is byte-identical to
    ``pack_payload(seq_slice(cfg, caches, bi*block, block))``."""
    if n_blocks <= 0:
        return []
    flat = np.asarray(_pack_blocks(cfg, caches, n_blocks, block))
    return [flat[bi] for bi in range(n_blocks)]


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1,))
def insert_blocks(cfg: ModelConfig, caches, rows: Sequence[jax.Array],
                  block: int):
    """Inverse of :func:`pack_blocks`: write ``rows``, row ``bi`` the EMS
    payload of tokens [bi*block, (bi+1)*block), into ``caches`` at token
    offset 0 with one update per leaf. The rows come as separate arrays
    and are stacked here, on the device: on the host, stacking a long
    prefix costs more than its transfer. ``caches`` is donated, so the
    write lands in place."""
    n_blocks = len(rows)
    blocks = jnp.stack(rows)
    template = jax.eval_shape(
        lambda c: seq_slice(cfg, c, 0, n_blocks * block), caches)
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        # leaf: (L, B, n_blocks*block, ...); its columns of a row ravel
        # (L, B, block, ...), as _pack_blocks laid them out.
        width = leaf.size // n_blocks
        x = blocks[:, off:off + width].reshape(
            (n_blocks,) + leaf.shape[:2] + (block,) + leaf.shape[3:])
        out.append(jnp.moveaxis(x, 0, 2).reshape(leaf.shape))
        off += width
    return seq_insert(cfg, caches, jax.tree.unflatten(treedef, out), 0)


def payload_token_nbytes(cfg: ModelConfig, caches) -> int:
    """Stored bytes per cached token: the size of a one-token
    :func:`seq_slice` payload as :func:`pack_payload` serializes it
    (float32 storage). EMS capacity sizing and bench byte accounting both
    derive per-block footprints from this instead of re-deriving model
    cache layouts by hand."""
    payload = seq_slice(cfg, caches, 0, 1)
    return sum(int(x.size) for x in jax.tree.leaves(payload)) * 4


def fingerprint(payload: Any) -> int:
    """Order-stable CRC32 over every array leaf's raw bytes — the
    integrity check :class:`~repro.serving.transfer.KVTransferEngine`
    verifies on delivery before a migrated/transferred payload is allowed
    to land in a destination cache. Non-array leaves (lengths folded into
    scalars etc.) are skipped exactly as :func:`cache_nbytes` skips them."""
    crc = 0
    for leaf in jax.tree.leaves(payload):
        if hasattr(leaf, "dtype"):
            crc = zlib.crc32(
                np.ascontiguousarray(np.asarray(leaf)).tobytes(), crc)
    return crc


def pack_request(cfg: ModelConfig, req_slice) -> np.ndarray:
    """Serialize one request's cache slice (a :func:`slice_request` result)
    into a contiguous byte buffer — the drain unit of cross-engine KV
    migration. Only batched leaves are packed (unbatched bookkeeping leaves
    such as ``length`` scalars stay engine-local, exactly as
    :func:`insert_request` leaves them untouched). Bytes are *viewed*, not
    cast, so the round trip through :func:`unpack_request` is bit-exact for
    every dtype."""
    axes = cache_batch_axes(cfg, req_slice)
    parts: List[np.ndarray] = []
    jax.tree.map(
        lambda leaf, ax: None if ax is None else parts.append(
            np.ascontiguousarray(np.asarray(leaf)).reshape(-1).view(np.uint8)),
        req_slice, axes)
    return np.concatenate(parts) if parts else np.zeros(0, np.uint8)


def unpack_request(cfg: ModelConfig, flat: np.ndarray, template):
    """Inverse of :func:`pack_request`. ``template`` is a shape/dtype
    reference slice from the *destination* engine (``slice_request`` of the
    target row); its unbatched leaves pass through unchanged."""
    axes = cache_batch_axes(cfg, template)
    offset = [0]

    def _take(leaf, ax):
        if ax is None:
            return leaf
        n = leaf.size * leaf.dtype.itemsize
        arr = np.frombuffer(flat[offset[0]:offset[0] + n].tobytes(),
                            dtype=leaf.dtype).reshape(leaf.shape)
        offset[0] += n
        return jnp.asarray(arr)

    out = jax.tree.map(_take, template, axes)
    if offset[0] != flat.size:
        raise ValueError(
            f"migration payload of {flat.size} bytes does not match the "
            f"destination cache layout ({offset[0]} bytes expected)")
    return out


def pack_payload(payload: Dict[str, Any]) -> np.ndarray:
    """Flatten a seq_slice payload to one contiguous byte buffer (the unit
    stored in the EMS pool)."""
    leaves = [np.asarray(x, np.float32).ravel() for x in jax.tree.leaves(payload)]
    return np.concatenate(leaves) if leaves else np.zeros(0, np.float32)


def payload_like(cfg: ModelConfig, batch: int, length: int, template) -> Dict[str, Any]:
    return seq_slice(cfg, template, 0, length)


def unpack_payload(flat: np.ndarray, template: Dict[str, Any]) -> Dict[str, Any]:
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(jnp.asarray(flat[off:off + n], jnp.float32).reshape(leaf.shape))
        off += n
    return jax.tree.unflatten(treedef, out)
