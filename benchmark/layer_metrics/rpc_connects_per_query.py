"""Layer: front door. `rpc.conn_opened` delta over the window per query:
Flight connections made inside users' queries. A served `scan_agg` query
makes five small RPCs to the one worker (two dispatches; a probe and the
root stream; a release); while each attempt connected they cost four
connections a query. With a pool of kept connections (`cluster/rpc.py`) a
warm cluster makes none: 0.0. Nothing to read in a program that does not
count its connections (it has no `rpc.conn_opened`: set-up makes the
client's own, so a program that counts has counted before the window)."""


def read(run: dict):
    from igloo_tpu.utils import tracing
    n = len(run["queries"])
    if not n or "rpc.conn_opened" not in tracing.counters():
        return None
    return run["counters"].get("rpc.conn_opened", 0) / n
