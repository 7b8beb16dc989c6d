"""The comparison that decides `correct`: an engine's Arrow table against the
plain reference's answer on the same data.

Copied from chip_smoke.py (`frame`, `compare`; PR 22 ran them on the chip)
and changed to return numbers, not messages: the harness prints each number
beside its limit. Rows compare in order (every multi-row query here has an
ORDER BY); columns pair by name, the rest in order.
"""
from __future__ import annotations

import numpy as np
import pandas as pd
import pyarrow as pa


def frame(table: pa.Table) -> pd.DataFrame:
    """Arrow table -> DataFrame with date columns as int days since the
    epoch, the reference's convention (oracle/tpch_pandas.py)."""
    return pd.DataFrame({
        f.name: (col.cast(pa.int32()).to_numpy()
                 if pa.types.is_date32(f.type) else col.to_pandas())
        for f, col in zip(table.schema, table.columns)})


def compare(got: pa.Table, want) -> tuple:
    """(largest relative error over float cells, number of wrong cells,
    [what differs]). `want` is the reference's DataFrame or a scalar. A
    missing or extra row or column counts every cell of the larger side as
    wrong; a non-finite float counts as wrong; every other float cell goes
    into the relative error, which the caller holds to the configuration's
    tolerance."""
    got = frame(got)
    if not isinstance(want, pd.DataFrame):
        want = pd.DataFrame({(got.columns[0] if len(got.columns) else "v"):
                             [want]})
    if got.shape != want.shape:
        cells = max(got.shape[0], want.shape[0]) * max(got.shape[1],
                                                       want.shape[1])
        return 0.0, max(cells, 1), [f"shape: got {got.shape}, "
                                    f"reference {want.shape}"]
    rest = [c for c in want.columns if c not in got.columns]
    worst, wrong, notes = 0.0, 0, []
    for name in got.columns:
        g = got[name].to_numpy()
        w = want[name if name in want.columns else rest.pop(0)].to_numpy()
        if g.dtype.kind == "f" or w.dtype.kind == "f":
            g, w = g.astype(np.float64), w.astype(np.float64)
            bad = ~np.isfinite(g)
            if bad.any():
                wrong += int(bad.sum())
                notes.append(f"{name}: {int(bad.sum())} non-finite")
            ok = ~bad
            rel = np.where(g[ok] == w[ok], 0.0, np.abs(g[ok] - w[ok])
                           / np.maximum(np.abs(w[ok]), 1e-300))
            err = float(rel.max(initial=0.0))
            if err > worst:
                worst = err
        else:
            differ = np.flatnonzero(g.astype(object) != w.astype(object))
            if len(differ):
                i = int(differ[0])
                wrong += len(differ)
                notes.append(f"{name}: {len(differ)} differ, first row {i}: "
                             f"got {g[i]!r}, reference {w[i]!r}")
    return worst, wrong, notes
