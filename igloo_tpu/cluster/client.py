"""Distributed client: Arrow Flight SQL against the coordinator.

Fills two reference stubs at once: `crates/client/src/main.rs:1-4` (an empty
binary that was meant to speak Flight SQL) and `pyigloo` (an empty PyO3 crate).
Any stock Arrow Flight client interoperates — this class is convenience, not
protocol: a stock client's `do_get(ticket=sql)` works from any language.

Every call carries the RPC policy's per-call deadline, so a hung coordinator
costs a bounded timeout instead of a wedged client; pass `deadline_s` to
`execute` for a per-query budget the COORDINATOR also enforces (it stops
dispatching fragments and releases worker results at the deadline), and
`qid` to make the query addressable by `cancel`.
"""
from __future__ import annotations

import json
import time
from typing import Optional

import pyarrow as pa
import pyarrow.flight as flight

from igloo_tpu.cluster import protocol, rpc, serving
from igloo_tpu.cluster.rpc import call_options as _call_options
from igloo_tpu.cluster.rpc import normalize as _normalize
from igloo_tpu.errors import IglooError
from igloo_tpu.utils import tracing


class DistributedClient:
    def __init__(self, addr: str, policy: Optional[rpc.RpcPolicy] = None):
        self.addr = _normalize(addr)
        self._policy = policy or rpc.default_policy()
        self._client = rpc.connect(self.addr)

    # --- health / metadata ---

    def ping(self) -> dict:
        return self._action("ping")

    def cluster_status(self) -> dict:
        return self._action("cluster_status")

    def last_metrics(self) -> dict:
        """Per-fragment metrics of the last distributed query (worker, rows,
        elapsed_s per fragment + totals), typed through the registry schema
        (cluster/protocol.py LAST_METRICS)."""
        return protocol.LAST_METRICS.parse(self._action("last_metrics"))

    def tables(self) -> list[str]:
        return self.cluster_status()["tables"]

    def active_queries(self) -> list[str]:
        """qids of in-flight distributed queries (cancel targets)."""
        return self._action("active_queries").get("queries", [])

    def serving_status(self) -> dict:
        """Admission queue / concurrency / HBM-reservation snapshot
        (docs/serving.md; shape: cluster/protocol.py SERVING_STATUS)."""
        return self._action("serving_status")

    def trace(self, trace_id: Optional[str] = None,
              qid: Optional[str] = None, fmt: str = "chrome") -> dict:
        """Stitched flight-recorder timeline by trace_id or qid (neither =
        the most recent query): Chrome-trace/Perfetto JSON by default,
        the raw span record with fmt="raw"
        (docs/observability.md#distributed-tracing)."""
        return self._action("trace", protocol.TRACE_REQUEST.build(
            trace_id=trace_id, qid=qid, format=fmt))

    def metrics_text(self) -> str:
        """Coordinator process + worker-aggregated fragment metrics,
        Prometheus text exposition."""
        return rpc.flight_action_raw(
            self.addr, "metrics",
            policy=self._policy).decode()

    def poll_info(self, sql: str) -> dict:
        """PollFlightInfo equivalent: planning completes eagerly, so the
        reply is always {"progress": 1.0, "complete": true}."""
        return self._action("poll_flight_info",
                            protocol.POLL_FLIGHT_INFO.build(sql=sql))

    # --- watchtower (docs/observability.md#watchtower) ---

    def metrics_history(self) -> list:
        """The fleet's sampler rings, source-labeled and merged by
        timestamp: the coordinator's own plus every live worker's."""
        return protocol.METRICS_HISTORY.parse(
            self._action("metrics_history"))["samples"]

    def events(self, min_severity: str = "info",
               limit: Optional[int] = None) -> list:
        """Cluster event journal, oldest first, at or above
        `min_severity` ("info" | "warn" | "error")."""
        return protocol.EVENTS_REPLY.parse(self._action(
            "events", protocol.EVENTS_REQUEST.build(
                min_severity=min_severity, limit=limit)))["events"]

    def slow_queries(self) -> list:
        """Baseline-anomaly escalation records (system.slow_queries)."""
        return protocol.SLOW_QUERIES_REPLY.parse(
            self._action("slow_queries"))["slow_queries"]

    def watch_status(self) -> dict:
        """One-call ops snapshot behind `igloo top`: qps/latency
        quantiles, admission state, workers, active queries, recent
        journal events and sampler rows."""
        return protocol.WATCH_STATUS.parse(self._action("watch_status"))

    # --- queries ---

    def execute(self, sql: str, deadline_s: Optional[float] = None,
                qid: Optional[str] = None, priority: Optional[int] = None,
                session: Optional[str] = None,
                busy_wait_s: Optional[float] = None,
                trace_id: Optional[str] = None) -> pa.Table:
        """One round trip: the ticket IS the SQL (do_get executes once).
        `deadline_s` bounds the query server-side (and this call, slightly
        padded so the coordinator's deadline fires first and reports
        properly); `qid` names it for `cancel`; `priority` (0 = interactive
        ... lower tiers) and `session` feed the coordinator's admission
        controller (docs/serving.md); `trace_id` names the query's stitched
        flight-recorder timeline (fetch it back with the `trace` action —
        docs/observability.md#distributed-tracing).

        Retry model: a SHED query (the coordinator's admission queue was
        full — `IGLOO_BUSY` marker) is retried with backoff honoring the
        server's retry-after hint until `busy_wait_s` (default 60 s, or the
        query deadline when one is set) — overload means bounded extra
        latency, not a failure. Other RETRYABLE transport failures
        (unavailable peer, timeout) use the policy's normal retry budget;
        fatal errors (the query itself failed) surface immediately.
        Retrying from scratch is safe: results materialize via read_all(),
        so no partial batches were consumed."""
        with tracing.span("client.execute"):
            # the registry coerces HERE, so a mistyped field fails
            # client-side with a ProtocolError naming it instead of
            # round-tripping to an opaque server error; unset fields are
            # omitted and a bare ticket collapses to the SQL itself
            # (stock-client wire compatibility)
            body = protocol.QUERY_TICKET.build(
                sql=sql, deadline_s=deadline_s, qid=qid, priority=priority,
                session=session, trace_id=trace_id)
            ticket = protocol.encode_query_ticket(body, sql)
            timeout = self._policy.stream_timeout_s if deadline_s is None \
                else deadline_s + min(5.0, self._policy.connect_timeout_s)
            if busy_wait_s is None:
                busy_wait_s = deadline_s if deadline_s is not None else 60.0
            return self._read_all(ticket, timeout,
                                  time.time() + busy_wait_s)

    def _read_all(self, ticket: str, timeout: float,
                  busy_deadline: float) -> pa.Table:
        """`execute`'s do_get under its retry model."""
        # SEPARATE budgets: sheds are bounded by busy_deadline only and must
        # not consume the transport retry budget — a client shed twice under
        # load still deserves its full policy budget for an unrelated
        # transient transport failure afterwards
        busy_attempt = 0
        attempt = 0
        while True:
            try:
                with tracing.span("client.wait") as wait:
                    try:
                        reader = self._client.do_get(
                            flight.Ticket(ticket.encode()),
                            _call_options(timeout_s=timeout))
                        return reader.read_all()
                    finally:
                        # a kind of its own: the coordinator's end of this
                        # call is `rpc.server_us.do_get`
                        rpc.count_call("client.do_get", wait.elapsed_s)
            except flight.FlightError as ex:
                msg = str(ex)
                if serving.BUSY_MARKER in msg:
                    # load shed: bounded-latency retry, not a failure
                    hint = serving.parse_retry_after(msg)
                    delay = hint if hint is not None \
                        else self._policy.backoff_s(busy_attempt + 1)
                    if time.time() + delay >= busy_deadline:
                        raise IglooError(_strip_flight(msg)) from None
                    busy_attempt += 1
                    tracing.counter("client.busy_retries")
                    time.sleep(delay)
                    continue
                if rpc.retryable(ex) and attempt < self._policy.retries:
                    attempt += 1
                    tracing.counter("rpc.retries")
                    time.sleep(self._policy.backoff_s(attempt))
                    continue
                raise IglooError(_strip_flight(msg)) from None

    sql = execute

    def cancel(self, qid: str) -> bool:
        """Cancel a running distributed query by the qid passed to
        `execute`; False when the coordinator no longer knows it."""
        return bool(self._action(
            "cancel_query",
            protocol.CANCEL_QUERY.build(qid=qid)).get("cancelled"))

    def schema(self, sql: str) -> pa.Schema:
        """Result schema WITHOUT executing (the reference runs the query to
        answer this — crates/api/src/lib.rs:90-98)."""
        desc = flight.FlightDescriptor.for_command(sql.encode())
        try:
            return self._client.get_schema(
                desc, _call_options(
                    timeout_s=self._policy.call_timeout_s)).schema
        except flight.FlightError as ex:
            raise IglooError(_strip_flight(str(ex))) from None

    # --- registration ---

    def register_table(self, name: str, table: pa.Table) -> None:
        """Upload an in-memory table (Flight do_put; reference: unimplemented)."""
        desc = flight.FlightDescriptor.for_path(name)
        writer, _ = self._client.do_put(
            desc, table.schema,
            _call_options(timeout_s=self._policy.stream_timeout_s))
        writer.write_table(table)
        writer.close()

    def register_parquet(self, name: str, path: str) -> None:
        self._action("register_table", protocol.REGISTER_TABLE.build(
            name=name, spec={"kind": "parquet", "path": path}))

    def register_csv(self, name: str, path: str, has_header: bool = True,
                     delimiter: str = ",") -> None:
        self._action("register_table", protocol.REGISTER_TABLE.build(
            name=name, spec={"kind": "csv", "path": path,
                             "has_header": has_header,
                             "delimiter": delimiter}))

    # --- plumbing ---

    def _action(self, name: str, payload: Optional[dict] = None) -> dict:
        body = json.dumps(payload).encode() if payload is not None else b""
        try:
            # `client.action.<name>`: a harness's or an operator's admin
            # calls are told from the calls a query makes
            with rpc.call(f"client.action.{name}"):
                results = list(self._client.do_action(
                    flight.Action(name, body),
                    _call_options(timeout_s=self._policy.call_timeout_s)))
        except flight.FlightError as ex:
            raise IglooError(_strip_flight(str(ex))) from None
        return json.loads(results[0].body.to_pybytes()) if results else {}

    def close(self) -> None:
        self._client.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _strip_flight(msg: str) -> str:
    # flight errors carry transport prefixes; keep the engine's message
    for marker in ("detail: ", "message: "):
        if marker in msg:
            msg = msg.split(marker, 1)[1]
    return msg.split(". gRPC client debug context")[0].strip()
