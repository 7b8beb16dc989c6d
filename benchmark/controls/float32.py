"""The control of `correct`: the plain reference, put in the program's place
and computed one precision below the float64 the configurations state, i.e.
with every float64 column cast to float32. The step that would tempt a later
PR (float64 is emulated on the TPU). `correct` has to come out false."""
import numpy as np
import pandas as pd
import pyarrow as pa

from compare import frame


def answers(kept: dict, traffic: dict, answers_from) -> dict:
    """{query: Arrow table}, as a deployment would have returned them."""
    frames = {}
    for name, tbl in kept.items():
        df = frame(tbl)
        floats = [c for c in df.columns if df[c].dtype == np.float64]
        frames[name] = df.astype({c: np.float32 for c in floats})
    out = {}
    for name, ans in answers_from(frames, traffic).items():
        if not isinstance(ans, pd.DataFrame):
            ans = pd.DataFrame({"value": [ans]})
        out[name] = pa.Table.from_pandas(ans, preserve_index=False)
    return out
