"""The wire-contract registry (cluster/protocol.py): typed round-trips,
coercion error messages, version tolerance (legacy payloads through declared
defaults), ticket folding, and the client surface for every registry action.
Fast tier — only the client-surface test opens a (worker-less) coordinator.
"""
import json

import pyarrow as pa
import pytest

from igloo_tpu.cluster import exchange, protocol, serde
from igloo_tpu.cluster.protocol import ProtocolError


# --- round trips -------------------------------------------------------------


def test_query_ticket_roundtrip_through_json():
    body = protocol.QUERY_TICKET.build(sql="SELECT 1", deadline_s=5,
                                       qid="q1", priority=0, session="s",
                                       trace_id="t1")
    wire = json.dumps(body)
    t = protocol.QUERY_TICKET.parse(wire)
    assert t == {"sql": "SELECT 1", "deadline_s": 5.0, "qid": "q1",
                 "priority": 0, "session": "s", "trace_id": "t1"}


def test_build_omits_unset_and_ticket_collapses_to_bare_sql():
    body = protocol.QUERY_TICKET.build(sql="SELECT 1", deadline_s=None,
                                       qid=None, priority=None, session=None,
                                       trace_id=None)
    assert body == {"sql": "SELECT 1"}
    assert protocol.encode_query_ticket(body, "SELECT 1") == "SELECT 1"
    # any extended field forces the JSON form
    body = protocol.QUERY_TICKET.build(sql="SELECT 1", priority=2)
    assert protocol.encode_query_ticket(body, "SELECT 1") != "SELECT 1"


def test_parse_applies_declared_defaults():
    t = protocol.parse_query_ticket("SELECT 1")
    assert t["priority"] == 1 and t["session"] == "" and t["qid"] is None


def test_typed_coercion_and_error_messages():
    # loosely-typed but coercible fields coerce ("5" -> 5.0, 7 -> "7")
    t = protocol.QUERY_TICKET.parse({"sql": "x", "deadline_s": "5",
                                     "qid": 7})
    assert t["deadline_s"] == 5.0 and t["qid"] == "7"
    # an uncoercible value names the message, the field, and both types
    with pytest.raises(ProtocolError, match=r"query_ticket.*'deadline_s'.*"
                                            r"expected float.*list"):
        protocol.QUERY_TICKET.parse({"sql": "x", "deadline_s": [5]})
    # strict fields do not coerce: 7 is not SQL
    with pytest.raises(ProtocolError, match=r"'sql'.*expected str"):
        protocol.QUERY_TICKET.parse({"sql": 7})
    with pytest.raises(ProtocolError, match="missing required field 'sql'"):
        protocol.QUERY_TICKET.parse({"deadline_s": 5})
    # an explicit JSON null is "not set": on a required field that is a
    # boundary error, never a NoneType crash deep in planning (review fix)
    with pytest.raises(ProtocolError, match="missing required field 'sql'"):
        protocol.QUERY_TICKET.parse('{"sql": null}')
    t = protocol.QUERY_TICKET.parse({"sql": "x", "priority": None})
    assert t["priority"] == 1  # null optional -> declared default
    with pytest.raises(ProtocolError, match="not valid JSON"):
        protocol.QUERY_TICKET.parse("{nope")


def test_build_rejects_undeclared_fields():
    with pytest.raises(ProtocolError, match="undeclared field 'deadline'"):
        protocol.QUERY_TICKET.build(sql="x", deadline=5)


def test_unknown_wire_fields_ride_through():
    # version tolerance: a NEWER peer's extra field must not break us
    t = protocol.WORKER_INFO.parse({"id": "w", "future_field": 3})
    assert t["future_field"] == 3 and t["devices"] == 1


def test_parse_defaults_are_isolated_per_call():
    a = protocol.RELEASE.parse({})
    b = protocol.RELEASE.parse({})
    a["ids"].append("x")
    assert b["ids"] == []


def test_sparse_messages_leave_absent_fields_absent():
    s = protocol.FRAGMENT_STATS.parse({"id": "f", "rows": 1,
                                       "elapsed_s": 0.5})
    assert "buckets" not in s and s["rows"] == 1
    with pytest.raises(ProtocolError, match="missing required field 'rows'"):
        protocol.FRAGMENT_STATS.parse({"id": "f", "elapsed_s": 0.5})


# --- exchange ticket ---------------------------------------------------------


def test_exchange_ticket_bare_and_bucketed():
    assert exchange.parse_ticket(b"abc123") == ("abc123", None, None)
    raw = exchange.make_ticket("abc123", bucket=3, nbuckets=8)
    assert exchange.parse_ticket(raw) == ("abc123", 3, 8)
    with pytest.raises(ProtocolError, match="missing required field 'frag'"):
        exchange.parse_ticket(b'{"bucket": 3}')


# --- worker_info (registration/heartbeat) ------------------------------------


def test_worker_info_legacy_payload_parses_through_defaults():
    """A pre-topology (single-device era) payload takes the registry
    defaults: devices=1, slots=0 — the planner sizes exactly as before
    two-level parallelism."""
    info = serde.worker_info_from_json({"id": "w0"})
    assert info == {"id": "w0", "addr": "", "devices": 1, "slots": 0,
                    "events": []}
    with pytest.raises(ProtocolError, match="missing required field 'id'"):
        serde.worker_info_from_json({"addr": "x"})


def test_heartbeat_payload_has_no_dead_ts_field():
    """Regression for the wire-contract true positive: heartbeats shipped a
    wall-clock `ts` no consumer ever read (the coordinator's last_seen is
    its own clock). The registry retired it; old payloads carrying it still
    parse (unknown-field tolerance)."""
    d = serde.worker_info_to_json("w", "addr", devices=2, slots=4)
    assert "ts" not in d and "ts" not in protocol.WORKER_INFO.fields
    legacy = serde.worker_info_from_json({"id": "w", "addr": "a",
                                          "ts": 123.0})
    assert legacy["id"] == "w"


# --- client surface for every registry action --------------------------------


def test_client_covers_control_actions():
    """Every coordinator control action has a typed client accessor (the
    flight-actions checker warns on registry actions with no in-package
    caller). Worker-less coordinator: queries run on the local fallback."""
    from igloo_tpu.cluster.client import DistributedClient
    from igloo_tpu.cluster.coordinator import CoordinatorServer
    coord = CoordinatorServer("grpc+tcp://127.0.0.1:0", use_jit=False)
    try:
        coord.register_table("t", pa.table({"a": [1, 2, 3]}))
        with DistributedClient(f"127.0.0.1:{coord.port}") as cl:
            assert cl.ping()["workers"] == 0
            assert "t" in cl.tables()
            assert cl.active_queries() == []
            st = cl.serving_status()
            assert st["enabled"] and st["running"] == 0
            info = cl.poll_info("SELECT a FROM t")
            assert info["complete"] is True and info["progress"] == 1.0
            assert "igloo_" in cl.metrics_text()
            out = cl.execute("SELECT sum(a) AS s FROM t", trace_id="tr-1")
            assert out.to_pydict() == {"s": [6]}
            tr = cl.trace(trace_id="tr-1")
            assert isinstance(tr.get("traceEvents"), list)
            raw = cl.trace(qid=None, fmt="raw")
            assert raw["trace_id"] == "tr-1"
    finally:
        coord.shutdown()
