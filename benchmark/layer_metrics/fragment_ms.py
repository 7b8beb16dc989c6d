"""Layer: worker. Mean over the window of the sum of a query's fragment
`elapsed_s` (client.last_metrics()); served deployments only."""


def read(run: dict):
    f = [q["info"]["fragment_s"] for q in run["queries"]
         if "fragment_s" in q["info"]]
    return 1e3 * sum(f) / len(f) if f else None
