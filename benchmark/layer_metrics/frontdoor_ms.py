"""Layer: front door. Mean over the window of client-side latency minus the
sum of that query's fragment `elapsed_s`: Flight, admission, fragment
planning and dispatch, result streaming. Served deployments only."""


def read(run: dict):
    d = [q["latency_s"] - q["info"]["fragment_s"] for q in run["queries"]
         if "fragment_s" in q["info"]]
    return 1e3 * sum(d) / len(d) if d else None
