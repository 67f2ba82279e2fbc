"""Row format "mixture" (a configuration without a `rows` key): dense
float32 rows of the configuration's Gaussian mixture (`data.make_rows`),
held as one (n, d) host array.

A row format module gives the fit generator and the reference everything
that depends on how rows are made and stored:

  make(seed, config, n, stream)   the host rows, from the seed;
  head(rows, n)                   the first n rows, in the same format;
  program_input(rows, block_rows, hold, devices)
                                  what `KernelKMeans.fit` is given: a host
                                  `BlockStore` ("host") or the rows placed
                                  once in device memory ("device");
  ref_chunks(rows)                the rows in order as dense float32 chunks
                                  of at most REF_CHUNK_ROWS rows, for the
                                  plain reference.
"""
from __future__ import annotations

import numpy as np

from . import data

REF_CHUNK_ROWS = 32768


def make(seed: int, config: dict, n: int, stream: int = data.STREAM_FIT) -> np.ndarray:
    return data.make_rows(seed, config, n, stream)


def head(rows: np.ndarray, n: int) -> np.ndarray:
    return rows[:n]


def program_input(rows: np.ndarray, block_rows: int, hold: str, devices: list):
    if hold == "host":
        from repro.stream.blockstore import BlockStore

        return BlockStore.from_array(rows, block_rows)
    if hold == "device" and len(devices) == 1:
        import jax

        return jax.block_until_ready(jax.device_put(rows, devices[0]))
    raise ValueError(f"mixture rows cannot be held as {hold!r} on {len(devices)} devices")


def ref_chunks(rows: np.ndarray):
    for lo in range(0, rows.shape[0], REF_CHUNK_ROWS):
        yield rows[lo:lo + REF_CHUNK_ROWS]
