"""Layer: session. Mean client-side latency minus mean device-busy time per
query (trace): what the host adds around the device's work in-process."""


def read(run: dict):
    t, q = run["trace"], run["queries"]
    if not t or not q:
        return None
    return 1e3 * (sum(x["latency_s"] for x in q) - t["busy_s"]) / len(q)
