"""Seeded scenes, their stored RTIC files, and an independent RTIC reader.

A configuration names its rasters (``"rasters": ["scene"]`` or
``["xs", "pan"]``) and gives each one ``<raster>_rows``, ``_cols``,
``_bands`` and ``_dtype``.  Pixels are a pure function of absolute
coordinates and of parameters drawn from the seed: the field of the
program's ``SyntheticScene`` (terrain, field polygons, roads, fine texture,
12-bit DNs), copied here so that the yardstick owns its inputs.  Every seed
gives the same sizes and value range; only phases and frequencies move.

The generator runs on the device in one jitted call per block of
``GEN_ROWS`` rows.  The same compiled block program regenerates any rows the
reference needs after the window, so the reference sees the very values that
were stored, without reading anything the program wrote.

Stored scenes live under ``chipbench/scenes/``, named by raster geometry,
storage tile and seed, and are reused by later runs; at most
``KEEP_SCENES`` seeds are kept per raster geometry.
"""
from __future__ import annotations

import functools
import json
import os
from pathlib import Path
from typing import Dict, List

import numpy as np

#: rows per generated block (also the unit the reference regenerates)
GEN_ROWS = 512
#: version of the pixel field: part of every stored scene's file name
FIELD_VERSION = "f1"
KEEP_SCENES = 12

RTIC_MAGIC = b"RTIC0001"
RTIC_HEADER = 4096


def seed_params(seed: int, salt: int) -> np.ndarray:
    """Per-seed phases and frequency of the field: [freq, row_off, col_off,
    cell_off], all float32-exact."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, int(salt)])
    return np.array(
        [
            rng.uniform(0.6, 1.4),
            float(rng.integers(0, 50_000)),
            float(rng.integers(0, 50_000)),
            float(rng.integers(0, 1_000)),
        ],
        np.float32,
    )


def raster_spec(config: dict, raster: str) -> dict:
    return {
        "rows": int(config[f"{raster}_rows"]),
        "cols": int(config[f"{raster}_cols"]),
        "bands": int(config[f"{raster}_bands"]),
        "dtype": np.dtype(config[f"{raster}_dtype"]),
        "salt": int(config.get(f"{raster}_salt", 0)),
    }


@functools.lru_cache(maxsize=None)
def _block_fn(cols: int, bands: int, dtype: np.dtype):
    """Jitted (row0, params) -> (GEN_ROWS, cols, bands) block of DNs."""
    import jax
    import jax.numpy as jnp

    def block(row0, p):
        freq, roff, coff, cell_off = p[0], p[1], p[2], p[3]
        rr = (jnp.arange(GEN_ROWS, dtype=jnp.float32) + row0.astype(jnp.float32)
              + roff)[:, None, None]
        cc = (jnp.arange(cols, dtype=jnp.float32) + coff)[None, :, None]
        band = jnp.arange(bands, dtype=jnp.float32)[None, None, :]
        terrain = 600.0 * (
            jnp.sin(rr * (0.002 * freq)) * jnp.cos(cc * 0.0017)
            + 0.5 * jnp.sin((rr + 2 * cc) * 0.0009)
        )
        cell = (jnp.floor(rr / 97.0) * 31.0 + jnp.floor(cc / 143.0) * 17.0
                + band * 7.0 + cell_off)
        fields = 900.0 * (jnp.sin(cell * 12.9898) * 0.5 + 0.5)
        road = 700.0 * jnp.exp(
            -(jnp.abs((rr * 0.37 + cc * 0.93) % 811.0 - 405.0) / 3.0)
        )
        tex = 120.0 * jnp.sin(rr * 0.9 + band) * jnp.cos(cc * 1.1 + band * 2.0)
        vals = 800.0 + 180.0 * band + terrain + fields + road + tex
        return jnp.clip(vals, 0.0, 4095.0).astype(dtype)

    return jax.jit(block)


class SceneGen:
    """Regenerates rows of one raster of one seed, block by block."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.params = seed_params(seed, spec["salt"])
        self._fn = _block_fn(spec["cols"], spec["bands"], spec["dtype"])
        self._blocks: Dict[int, np.ndarray] = {}

    def block(self, k: int, keep: bool = True) -> np.ndarray:
        if k in self._blocks:
            return self._blocks[k]
        out = np.asarray(self._fn(np.int32(k * GEN_ROWS), self.params))
        valid = min(GEN_ROWS, self.spec["rows"] - k * GEN_ROWS)
        out = out[:valid]
        if keep:
            self._blocks[k] = out
        return out

    def rows(self, r0: int, r1: int) -> np.ndarray:
        """Rows [r0, r1) of the raster, r0 >= 0 and r1 <= rows."""
        parts = [
            self.block(k)
            for k in range(r0 // GEN_ROWS, (r1 - 1) // GEN_ROWS + 1)
        ]
        cat = np.concatenate(parts, axis=0)
        base = (r0 // GEN_ROWS) * GEN_ROWS
        return cat[r0 - base:r1 - base]

    def rows_edge(self, r0: int, r1: int, pad_cols: int = 0) -> np.ndarray:
        """Rows [r0, r1), possibly outside the raster, edge-replicated, and
        ``pad_cols`` edge-replicated columns on either side."""
        n = self.spec["rows"]
        a, b = max(r0, 0), min(r1, n)
        arr = self.rows(a, b)
        return np.pad(
            arr, [(a - r0, r1 - b), (pad_cols, pad_cols), (0, 0)], mode="edge"
        )


def scene_dir(root: Path) -> Path:
    return Path(root) / "chipbench" / "scenes"


def ensure_scene(root: Path, config: dict, raster: str, seed: int) -> Path:
    """The stored RTIC file of one raster for this seed; written through the
    program's ``TileWriter`` when missing (set-up), reused otherwise."""
    from repro.core.process_object import GeoTransform, ImageInfo
    from repro.core.region import ImageRegion
    from repro.raster.tiled import TileWriter

    spec = raster_spec(config, raster)
    d = scene_dir(root)
    d.mkdir(parents=True, exist_ok=True)
    # keyed by what the pixels depend on, so deployments of one product
    # (one chip, a tile grid) share their stored scenes
    stem = (f"{spec['rows']}x{spec['cols']}x{spec['bands']}.{spec['dtype'].str[1:]}"
            f".s{spec['salt']}.t{config['storage_tile']}.{FIELD_VERSION}")
    path = d / f"{stem}.{seed}.rtic"
    if path.exists():
        os.utime(path)
        return path
    _evict(d, stem)
    tmp = path.with_suffix(".tmp")
    gsd = float(config.get(f"{raster}_gsd_m", 1.0))
    info = ImageInfo(
        spec["rows"], spec["cols"], spec["bands"], spec["dtype"],
        GeoTransform(spacing_x=gsd, spacing_y=-gsd),
    )
    writer = TileWriter(str(tmp), tile_rows=int(config["storage_tile"]), levels=1)
    writer.begin(info)
    gen = SceneGen(spec, seed)
    for k in range(-(-spec["rows"] // GEN_ROWS)):
        data = gen.block(k, keep=False)
        writer.consume(ImageRegion((k * GEN_ROWS, 0), data.shape[:2]), data)
    writer.end()
    # on disk before it is used, so that its writeback never runs beside a
    # later window
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, path)
    fd = os.open(d, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    return path


def _evict(d: Path, stem: str) -> None:
    """Keep the newest KEEP_SCENES - 1 stored seeds of this raster."""
    olds = sorted(d.glob(f"{stem}.*.rtic"), key=lambda p: p.stat().st_mtime)
    for p in olds[: max(0, len(olds) - (KEEP_SCENES - 1))]:
        p.unlink(missing_ok=True)
    for p in d.glob(f"{stem}.*.tmp"):
        p.unlink(missing_ok=True)


class RticFile:
    """Reads level 0 of an RTIC container: the fixed header, the footer
    index and the tile blobs, parsed here from the format alone."""

    def __init__(self, path: Path):
        self.fd = os.open(path, os.O_RDONLY)
        head = os.pread(self.fd, RTIC_HEADER, 0)
        if not head.startswith(RTIC_MAGIC):
            raise ValueError(f"{path}: not an RTIC container")
        meta = json.loads(head[len(RTIC_MAGIC):].rstrip(b"\0").decode())
        self.rows, self.cols, self.bands = meta["rows"], meta["cols"], meta["bands"]
        self.dtype = np.dtype(meta["dtype"])
        self.tr, self.tc = meta["tile_rows"], meta["tile_cols"]
        index = json.loads(
            os.pread(self.fd, meta["index_length"], meta["index_offset"]).decode()
        )
        self.tiles: Dict[str, List[int]] = index["levels"][0]["tiles"]

    def read_rows(self, r0: int, r1: int) -> np.ndarray:
        out = np.zeros((r1 - r0, self.cols, self.bands), self.dtype)
        for ty in range(r0 // self.tr, (r1 - 1) // self.tr + 1):
            t0 = ty * self.tr
            th = min(self.tr, self.rows - t0)
            for tx in range(-(-self.cols // self.tc)):
                c0 = tx * self.tc
                tw = min(self.tc, self.cols - c0)
                if f"{ty},{tx}" not in self.tiles:
                    continue  # never written: its pixels read as zero
                off, length = self.tiles[f"{ty},{tx}"]
                blob = os.pread(self.fd, length, off)
                tile = np.frombuffer(blob, self.dtype).reshape(th, tw, self.bands)
                a, b = max(r0, t0), min(r1, t0 + th)
                out[a - r0:b - r0, c0:c0 + tw] = tile[a - t0:b - t0]
        return out

    def close(self) -> None:
        os.close(self.fd)
