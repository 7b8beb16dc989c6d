"""Plan serialization — the wire format the reference faked.

The reference's `serialize_plan` returns empty bytes and `deserialize_batch`
fabricates a dummy 3-row batch (crates/coordinator/src/distributed_executor.rs:
203-222, gap G1). Here the fragment payload is REAL: a bound logical plan tree
(nodes + typed expressions + schemas) round-trips through JSON; table
references resolve against the receiving side's catalog (fragment results are
registered as `__frag_<id>` tables before execution). Result batches travel as
Arrow IPC streams, matching the reference's intended RecordBatchMessage
(distributed.proto:53-57) but with a codec that actually exists.
"""
from __future__ import annotations

import io
from typing import Optional

import pyarrow as pa

from igloo_tpu import types as T
from igloo_tpu.cluster import protocol
from igloo_tpu.errors import PlanError
from igloo_tpu.plan import expr as E
from igloo_tpu.plan import logical as L
from igloo_tpu.sql.ast import JoinType

# --- types / schema ---


def dtype_to_json(d: Optional[T.DataType]) -> Optional[str]:
    return None if d is None else d.id.value


def dtype_from_json(s: Optional[str]) -> Optional[T.DataType]:
    return None if s is None else T.DataType(T.TypeId(s))


def schema_to_json(s: T.Schema) -> list:
    return [[f.name, f.dtype.id.value, f.nullable] for f in s.fields]


def schema_from_json(j: list) -> T.Schema:
    return T.Schema([T.Field(n, T.DataType(T.TypeId(t)), bool(nl))
                     for n, t, nl in j])


# --- expressions ---


def expr_to_json(e: Optional[E.Expr]):
    if e is None:
        return None
    d: dict = {"t": type(e).__name__, "dt": dtype_to_json(e.dtype)}
    if isinstance(e, E.Column):
        d.update(name=e.name, index=e.index)
    elif isinstance(e, E.Literal):
        d.update(value=e.value, lt=dtype_to_json(e.literal_type))
    elif isinstance(e, E.Interval):
        d.update(days=e.days, months=e.months)
    elif isinstance(e, E.Binary):
        d.update(op=e.op.value, left=expr_to_json(e.left),
                 right=expr_to_json(e.right))
    elif isinstance(e, (E.Not, E.Negate)):
        d.update(operand=expr_to_json(e.operand))
    elif isinstance(e, E.IsNull):
        d.update(operand=expr_to_json(e.operand), negated=e.negated)
    elif isinstance(e, E.Cast):
        d.update(operand=expr_to_json(e.operand), to=dtype_to_json(e.to))
    elif isinstance(e, E.Case):
        d.update(whens=[[expr_to_json(c), expr_to_json(v)] for c, v in e.whens],
                 else_=expr_to_json(e.else_))
    elif isinstance(e, E.InList):
        d.update(operand=expr_to_json(e.operand),
                 items=[expr_to_json(i) for i in e.items], negated=e.negated)
    elif isinstance(e, E.Like):
        d.update(operand=expr_to_json(e.operand), pattern=e.pattern,
                 negated=e.negated, ci=e.case_insensitive)
    elif isinstance(e, E.Func):
        d.update(name=e.name, args=[expr_to_json(a) for a in e.args])
    elif isinstance(e, E.Aggregate):
        d.update(func=e.func.value, arg=expr_to_json(e.arg),
                 distinct=e.distinct)
    elif isinstance(e, E.Alias):
        d.update(operand=expr_to_json(e.operand), alias=e.alias)
    elif isinstance(e, E.Window):
        d.update(func=e.func, agg=expr_to_json(e.agg),
                 args=[expr_to_json(a) for a in e.args],
                 partition=[expr_to_json(x) for x in e.partition_by],
                 order=[expr_to_json(x) for x in e.order_by],
                 ascending=e.ascending, nulls_first=e.nulls_first)
    elif isinstance(e, E.ScalarSubquery):
        if not isinstance(e.query, L.LogicalPlan):
            raise PlanError("cannot serialize unbound scalar subquery")
        d.update(plan=plan_to_json(e.query))
    else:
        raise PlanError(f"cannot serialize expression {type(e).__name__}")
    return d


def expr_from_json(d) -> Optional[E.Expr]:
    if d is None:
        return None
    t = d["t"]
    if t == "Column":
        e: E.Expr = E.Column(name=d["name"], index=d["index"])
    elif t == "Literal":
        e = E.Literal(value=d["value"], literal_type=dtype_from_json(d["lt"]))
    elif t == "Interval":
        e = E.Interval(days=d["days"], months=d["months"])
    elif t == "Binary":
        e = E.Binary(op=E.BinOp(d["op"]), left=expr_from_json(d["left"]),
                     right=expr_from_json(d["right"]))
    elif t == "Not":
        e = E.Not(operand=expr_from_json(d["operand"]))
    elif t == "Negate":
        e = E.Negate(operand=expr_from_json(d["operand"]))
    elif t == "IsNull":
        e = E.IsNull(operand=expr_from_json(d["operand"]), negated=d["negated"])
    elif t == "Cast":
        e = E.Cast(operand=expr_from_json(d["operand"]),
                   to=dtype_from_json(d["to"]))
    elif t == "Case":
        e = E.Case(whens=[(expr_from_json(c), expr_from_json(v))
                          for c, v in d["whens"]],
                   else_=expr_from_json(d["else_"]))
    elif t == "InList":
        e = E.InList(operand=expr_from_json(d["operand"]),
                     items=[expr_from_json(i) for i in d["items"]],
                     negated=d["negated"])
    elif t == "Like":
        e = E.Like(operand=expr_from_json(d["operand"]), pattern=d["pattern"],
                   negated=d["negated"], case_insensitive=d["ci"])
    elif t == "Func":
        e = E.Func(name=d["name"], args=[expr_from_json(a) for a in d["args"]])
    elif t == "Window":
        e = E.Window(func=d["func"], agg=expr_from_json(d["agg"]),
                     args=[expr_from_json(a) for a in d["args"]],
                     partition_by=[expr_from_json(x) for x in d["partition"]],
                     order_by=[expr_from_json(x) for x in d["order"]],
                     ascending=d["ascending"], nulls_first=d["nulls_first"])
    elif t == "Aggregate":
        e = E.Aggregate(func=E.AggFunc(d["func"]), arg=expr_from_json(d["arg"]),
                        distinct=d["distinct"])
    elif t == "Alias":
        e = E.Alias(operand=expr_from_json(d["operand"]), alias=d["alias"])
    elif t == "ScalarSubquery":
        e = E.ScalarSubquery(query=None)  # plan attached below
        e.query = _PLAN_PLACEHOLDER(d["plan"])
    else:
        raise PlanError(f"cannot deserialize expression {t}")
    e.dtype = dtype_from_json(d["dt"])
    return e


class _PLAN_PLACEHOLDER:
    """Deferred subquery plan: resolved by plan_from_json's catalog pass."""

    def __init__(self, json_plan):
        self.json_plan = json_plan


# --- plans ---


def plan_to_json(p: L.LogicalPlan) -> dict:
    d: dict = {"t": type(p).__name__, "schema": schema_to_json(p.schema)}
    if isinstance(p, L.Scan):
        d.update(table=p.table, projection=p.projection,
                 pushed=[expr_to_json(f) for f in p.pushed_filters],
                 partition=getattr(p, "partition", None),
                 partition_token=getattr(p, "partition_token", None))
        if getattr(p, "bucket", None) is not None:
            d.update(bucket=p.bucket, buckets=p.buckets)
    elif isinstance(p, L.Filter):
        d.update(input=plan_to_json(p.input), predicate=expr_to_json(p.predicate))
    elif isinstance(p, L.Project):
        d.update(input=plan_to_json(p.input),
                 exprs=[expr_to_json(e) for e in p.exprs], names=p.names)
    elif isinstance(p, L.Aggregate):
        d.update(input=plan_to_json(p.input),
                 groups=[expr_to_json(e) for e in p.group_exprs],
                 group_names=p.group_names,
                 aggs=[expr_to_json(a) for a in p.aggs], agg_names=p.agg_names)
        if p.distinct_stage:
            d.update(distinct_stage=p.distinct_stage)
    elif isinstance(p, L.Join):
        d.update(left=plan_to_json(p.left), right=plan_to_json(p.right),
                 join_type=p.join_type.value,
                 lk=[expr_to_json(e) for e in p.left_keys],
                 rk=[expr_to_json(e) for e in p.right_keys],
                 residual=expr_to_json(p.residual))
    elif isinstance(p, L.Window):
        d.update(input=plan_to_json(p.input),
                 partition=[expr_to_json(e) for e in p.partition_exprs],
                 order=[expr_to_json(e) for e in p.order_exprs],
                 ascending=p.ascending, nulls_first=p.nulls_first,
                 funcs=[expr_to_json(e) for e in p.funcs], names=p.names)
    elif isinstance(p, L.Sort):
        d.update(input=plan_to_json(p.input),
                 keys=[expr_to_json(e) for e in p.keys],
                 ascending=p.ascending, nulls_first=p.nulls_first)
    elif isinstance(p, L.Limit):
        d.update(input=plan_to_json(p.input), limit=p.limit, offset=p.offset)
    elif isinstance(p, L.Distinct):
        d.update(input=plan_to_json(p.input))
    elif isinstance(p, L.Union):
        d.update(inputs=[plan_to_json(c) for c in p.inputs])
    elif isinstance(p, L.SetOpJoin):
        d.update(left=plan_to_json(p.left), right=plan_to_json(p.right),
                 anti=p.anti)
    elif isinstance(p, L.Values):
        d.update(rows=[list(r) for r in p.rows])
    elif isinstance(p, L.Exchange):
        d.update(input=plan_to_json(p.input), keys=list(p.keys),
                 buckets=p.buckets)
        if p.salt_role is not None:
            d.update(salt_bucket=p.salt_bucket, salt=p.salt,
                     salt_role=p.salt_role)
    else:
        raise PlanError(f"cannot serialize plan node {type(p).__name__}")
    return d


def plan_from_json(d: dict, catalog) -> L.LogicalPlan:
    """JSON -> bound plan; Scan providers resolve against `catalog`."""
    t = d["t"]
    schema = schema_from_json(d["schema"])
    if t == "Scan":
        p: L.LogicalPlan = L.Scan(
            table=d["table"], provider=catalog.get(d["table"]),
            projection=d["projection"],
            pushed_filters=[expr_from_json(f) for f in d["pushed"]])
        if d.get("partition") is not None:
            p.partition = tuple(d["partition"])  # type: ignore[attr-defined]
        p.partition_token = d.get("partition_token")  # type: ignore[attr-defined]
        if d.get("bucket") is not None:
            p.bucket = d["bucket"]    # type: ignore[attr-defined]
            p.buckets = d["buckets"]  # type: ignore[attr-defined]
    elif t == "Filter":
        p = L.Filter(input=plan_from_json(d["input"], catalog),
                     predicate=_rx(d["predicate"], catalog))
    elif t == "Project":
        p = L.Project(input=plan_from_json(d["input"], catalog),
                      exprs=[_rx(e, catalog) for e in d["exprs"]],
                      names=list(d["names"]))
    elif t == "Aggregate":
        p = L.Aggregate(input=plan_from_json(d["input"], catalog),
                        group_exprs=[_rx(e, catalog) for e in d["groups"]],
                        group_names=list(d["group_names"]),
                        aggs=[_rx(a, catalog) for a in d["aggs"]],
                        agg_names=list(d["agg_names"]),
                        distinct_stage=d.get("distinct_stage", 0))
    elif t == "Join":
        p = L.Join(left=plan_from_json(d["left"], catalog),
                   right=plan_from_json(d["right"], catalog),
                   join_type=JoinType(d["join_type"]),
                   left_keys=[_rx(e, catalog) for e in d["lk"]],
                   right_keys=[_rx(e, catalog) for e in d["rk"]],
                   residual=_rx(d["residual"], catalog))
    elif t == "Window":
        p = L.Window(input=plan_from_json(d["input"], catalog),
                     partition_exprs=[_rx(e, catalog) for e in d["partition"]],
                     order_exprs=[_rx(e, catalog) for e in d["order"]],
                     ascending=d["ascending"], nulls_first=d["nulls_first"],
                     funcs=[_rx(e, catalog) for e in d["funcs"]],
                     names=d["names"])
    elif t == "Sort":
        p = L.Sort(input=plan_from_json(d["input"], catalog),
                   keys=[_rx(e, catalog) for e in d["keys"]],
                   ascending=list(d["ascending"]),
                   nulls_first=list(d["nulls_first"]))
    elif t == "Limit":
        p = L.Limit(input=plan_from_json(d["input"], catalog),
                    limit=d["limit"], offset=d["offset"])
    elif t == "Distinct":
        p = L.Distinct(input=plan_from_json(d["input"], catalog))
    elif t == "Union":
        p = L.Union(inputs=[plan_from_json(c, catalog) for c in d["inputs"]])
    elif t == "SetOpJoin":
        p = L.SetOpJoin(left=plan_from_json(d["left"], catalog),
                        right=plan_from_json(d["right"], catalog),
                        anti=d["anti"])
    elif t == "Values":
        p = L.Values(rows=[list(r) for r in d["rows"]])
    elif t == "Exchange":
        p = L.Exchange(input=plan_from_json(d["input"], catalog),
                       keys=list(d["keys"]), buckets=d["buckets"],
                       salt_bucket=d.get("salt_bucket"),
                       salt=d.get("salt", 1),
                       salt_role=d.get("salt_role"))
    else:
        raise PlanError(f"cannot deserialize plan node {t}")
    p.schema = schema
    return p


def _rx(j, catalog) -> Optional[E.Expr]:
    """expr_from_json + resolve deferred subquery plans against the catalog."""
    e = expr_from_json(j)
    if e is None:
        return None
    for n in E.walk(e):
        if isinstance(n, E.ScalarSubquery) and \
                isinstance(n.query, _PLAN_PLACEHOLDER):
            n.query = plan_from_json(n.query.json_plan, catalog)
    return e


# --- worker info (registration / heartbeat payloads) ---


def worker_info_to_json(worker_id: str, addr: str, devices: int = 1,
                        slots: int = 0, events: Optional[list] = None) -> dict:
    """The registration/heartbeat payload, built through the protocol
    registry (cluster/protocol.py WORKER_INFO) so both sides of the wire
    share one declaration: `devices` is the size of the worker's LOCAL mesh
    (1 = single-device) — the topology number the distributed planner sizes
    bucket counts and placement with (bucket count scales with hosts, shard
    count with chips, docs/distributed.md) — and `slots` its execution-slot
    bound. `events` is the watchtower journal batch riding the heartbeat
    (cluster/events.drain_forward; omitted when empty so registration and
    legacy payloads stay byte-identical). (The pre-PR14 heartbeat also
    shipped a wall-clock `ts` no consumer ever read; the wire-contract
    checker retired it.)"""
    if events:
        return protocol.WORKER_INFO.build(id=worker_id, addr=addr,
                                          devices=int(max(devices, 1)),
                                          slots=int(slots), events=events)
    return protocol.WORKER_INFO.build(id=worker_id, addr=addr,
                                      devices=int(max(devices, 1)),
                                      slots=int(slots))


def worker_info_from_json(d: dict) -> dict:
    """Decode with version tolerance (the registry's declared defaults): a
    worker predating the topology fields — or a hand-rolled client —
    registers as single-device, which keeps the planner's sizing exactly as
    it was before two-level parallelism."""
    info = protocol.WORKER_INFO.parse(d)
    return {"id": info["id"], "addr": info["addr"],
            "devices": int(info["devices"] or 1),
            "slots": int(info["slots"] or 0),
            "events": list(info["events"] or [])}


# --- provider specs (how a worker re-creates a coordinator table) ---


def provider_to_spec(provider) -> Optional[dict]:
    """Shippable description of a table provider, or None if the provider
    cannot be reconstructed remotely (then its data ships as Arrow IPC)."""
    from igloo_tpu.catalog import MemTable
    from igloo_tpu.connectors.csv import CsvTable
    from igloo_tpu.connectors.iceberg import IcebergTable
    from igloo_tpu.connectors.parquet import ParquetTable
    if isinstance(provider, ParquetTable):
        return {"kind": "parquet", "path": provider.path}
    if isinstance(provider, CsvTable):
        return {"kind": "csv", "path": provider.path,
                "has_header": provider.has_header,
                "delimiter": provider.delimiter}
    if isinstance(provider, IcebergTable):
        return {"kind": "iceberg", "path": provider.path}
    if isinstance(provider, MemTable):
        import base64
        # partition count rides along: the planner strides the COORDINATOR
        # provider's partitions, so a worker rebuilding the table must slice
        # read_partition identically or striped scans return wrong rows
        return {"kind": "ipc", "partitions": provider.num_partitions(),
                "data": base64.b64encode(table_to_ipc(provider.read())).decode()}
    return None


def provider_from_spec(spec: dict):
    kind = spec["kind"]
    if kind == "parquet":
        from igloo_tpu.connectors.parquet import ParquetTable
        return ParquetTable(spec["path"])
    if kind == "csv":
        from igloo_tpu.connectors.csv import CsvTable
        return CsvTable(spec["path"], has_header=spec.get("has_header", True),
                        delimiter=spec.get("delimiter", ","))
    if kind == "iceberg":
        from igloo_tpu.connectors.iceberg import IcebergTable
        return IcebergTable(spec["path"])
    if kind == "ipc":
        import base64
        from igloo_tpu.catalog import MemTable
        return MemTable(table_from_ipc(base64.b64decode(spec["data"])),
                        partitions=spec.get("partitions", 1))
    raise PlanError(f"unknown provider spec kind: {kind}")


# --- Arrow IPC result codec ---


def table_to_ipc(table: pa.Table) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue()


def table_from_ipc(data: bytes) -> pa.Table:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return r.read_all()
