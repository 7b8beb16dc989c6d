"""95th percentile of the client-side latency of ALL queries of the window
(host clock, call to whole Arrow table held). For cells whose window holds
some hundreds of queries; with a few tens it would be the maximum."""
import numpy as np


def read(run: dict):
    lat = [q["latency_s"] for q in run["queries"]]
    return float(np.percentile(lat, 95)) if lat else None
