"""The BSBM dataset at a scale no encoder reaches in a run's set-up: one
encoded base dump, repeated in tiles whose instances are renamed.

Tile ``t`` of ``T`` is the base's planes with every term that is an IRI
under ``bsbm.INST`` renamed where it stands as subject or object: its id
plus ``t * n_terms``, its content hash remixed with ``t`` (``retag``).
Tile 0 is the base itself.  Predicates, the vocabulary, literals and
external IRIs repeat across tiles, and every length is the base's, so the
statements of different tiles are distinct and the predicate sketch sees
the base's predicates.

One function in two forms: ``tile`` (NumPy, for the reference, in worker
processes that never import JAX) and ``device_tiles`` (one program per
device: each writes its own tiles, one at a time, from a replicated copy
of the base into its rows of the row-sharded output, with no full-size
intermediate and nothing of the output on the host).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from generators import bsbm
from reference.encoder import (COL_O, COL_O_HASH, COL_S, COL_S_HASH,
                               N_PLANES, Encoder)

RENAME_S, RENAME_O = 1, 2       # bits of ``Base.renamed``: which positions
TILE_SALT = 0x9E3779B1          # a tile's number, spread over 32 bits
_INST = "<" + bsbm.INST


@dataclasses.dataclass
class Base:
    """The encoded base dump, and for each of its rows which positions
    hold a term that the tiles rename."""
    planes: np.ndarray          # (rows, N_PLANES) int32
    renamed: np.ndarray         # (rows,) int32, RENAME_S | RENAME_O
    n_terms: int

    @property
    def rows(self) -> int:
        return self.planes.shape[0]

    def save(self, path: str) -> None:
        np.savez(path, planes=self.planes, renamed=self.renamed,
                 n_terms=self.n_terms)

    @classmethod
    def load(cls, path: str) -> "Base":
        with np.load(path) as f:
            return cls(f["planes"], f["renamed"], int(f["n_terms"]))


def base(lines: list[str], base_namespaces) -> Base:
    """The base from a dump's N-Triples lines (``<s> <p> o .``, as
    ``bsbm`` writes them), encoded by the reference encoder."""
    enc = Encoder(base_namespaces)
    planes = enc.encode_lines(lines)
    renamed = np.zeros(len(lines), np.int32)
    for i, line in enumerate(lines):
        s, _, rest = line.split(" ", 2)
        renamed[i] = ((RENAME_S if s.startswith(_INST) else 0)
                      | (RENAME_O if rest.startswith(_INST) else 0))
    return Base(planes, renamed, enc.n_terms)


def _fmix32(x):
    """murmur3 fmix32 over uint32 lanes, NumPy or JAX."""
    u32 = x.dtype.type
    x = x ^ (x >> u32(16))
    x = x * u32(0x85EBCA6B)
    x = x ^ (x >> u32(13))
    x = x * u32(0xC2B2AE35)
    return x ^ (x >> u32(16))


def retag(h: np.ndarray, t: int) -> np.ndarray:
    """Content hashes (uint32) of tile ``t``'s renamed terms."""
    if t == 0:
        return h
    return _fmix32(h ^ _fmix32(np.full(1, t, np.uint32)
                               * np.uint32(TILE_SALT)))


def tile(b: Base, t: int) -> np.ndarray:
    """The planes of tile ``t`` (NumPy)."""
    out = b.planes.copy(order="K")     # the base's memory order
    if t == 0:
        return out
    for bit, col_id, col_hash in ((RENAME_S, COL_S, COL_S_HASH),
                                  (RENAME_O, COL_O, COL_O_HASH)):
        m = (b.renamed & bit) != 0
        out[m, col_id] += t * b.n_terms
        out[m, col_hash] = retag(out[m, col_hash].view(np.uint32),
                                 t).view(np.int32)
    return out


def tiles_program(rows: int, n_terms: int, n_tiles: int, mesh):
    """The jitted program of ``device_tiles``: (base planes, renamed),
    both replicated over ``mesh``, to the tiles."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if n_tiles * n_terms >= 1 << 31:
        raise ValueError(f"{n_tiles} tiles of {n_terms} terms overflow "
                         f"int32 term ids")
    axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    if n_tiles % n_dev:
        raise ValueError(f"{n_tiles} tiles do not split over {n_dev} "
                         f"devices")
    per = n_tiles // n_dev

    def u32(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint32)

    def build(planes, renamed):
        col = jnp.arange(N_PLANES)[None, :]
        rs = ((renamed & RENAME_S) != 0)[:, None]
        ro = ((renamed & RENAME_O) != 0)[:, None]
        ids = ((col == COL_S) & rs) | ((col == COL_O) & ro)
        hashes = ((col == COL_S_HASH) & rs) | ((col == COL_O_HASH) & ro)
        first = jax.lax.axis_index(axes) * per

        def one(i, out):        # tile first + i, written in place
            t = first + i
            tagged = _fmix32(u32(planes)
                             ^ _fmix32(u32(t) * np.uint32(TILE_SALT)))
            tagged = jnp.where(t == 0, planes, jax.lax.bitcast_convert_type(
                tagged, jnp.int32))
            tile_t = jnp.where(ids, planes + t * n_terms,
                               jnp.where(hashes, tagged, planes))
            return jax.lax.dynamic_update_slice_in_dim(out, tile_t, i * rows,
                                                       axis=0)

        return jax.lax.fori_loop(
            0, per, one, jnp.zeros((per * rows, N_PLANES), jnp.int32))

    return jax.jit(jax.shard_map(build, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(axes), check_vma=False))


def device_tiles(b: Base, n_tiles: int, mesh):
    """The ``n_tiles`` tiles, in order, as one ``(n_tiles * rows,
    N_PLANES)`` int32 ``jax.Array`` row-sharded over every axis of
    ``mesh``: device ``d`` holds tiles ``d * k`` to ``(d + 1) * k - 1``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    fn = tiles_program(b.rows, b.n_terms, n_tiles, mesh)
    everywhere = NamedSharding(mesh, P())
    return fn(jax.device_put(b.planes, everywhere),
              jax.device_put(b.renamed, everywhere))


def device_tile(planes, b: Base, t: int) -> np.ndarray:
    """Tile ``t`` read back from ``device_tiles``' output, from the
    device that holds it."""
    lo = t * b.rows
    for shard in planes.addressable_shards:
        start = shard.index[0].start or 0
        if start <= lo < start + shard.data.shape[0]:
            return np.asarray(shard.data[lo - start:lo - start + b.rows])
    raise IndexError(f"no device holds tile {t}")
