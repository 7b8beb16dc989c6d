"""The per-layer metrics that read the program's `span_us.*` self-time
counters (span_time.py over span_layers.json), on synthetic runs with known
answers; and every span of the program's catalog has exactly one layer."""
import importlib.util
import os
import re

import pytest
import span_time
from conftest import BENCH, ROOT

READERS = ("frontdoor_self_ms", "worker_self_ms", "session_self_ms",
           "programs_host_ms", "retrace_ms", "device_wait_ms",
           "unattributed_ms")


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"layer_metrics_{name}",
        os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_of(counters: dict, latencies=(1.0, 3.0)) -> dict:
    return {"queries": [{"name": "q", "latency_s": s, "info": {}}
                        for s in latencies],
            "counters": counters, "trace": None}


# two queries, 4 s of client latency; microseconds by span
SERVED = {
    "jit.miss": 2,                                   # not a span: ignored
    "span_us.client.execute": 4_000,                 # front door
    "span_us.serving.queue": 1_000,
    "span_us.coordinator.plan": 20_000,
    "span_us.fetch": 5_000,
    "span_us.grace.distributed": 2_000,              # exact beats grace.*
    "span_us.execute_fragment": 6_000,               # worker
    "span_us.fragment.plan": 30_000,
    "span_us.exchange.fetch": 4_000,
    "span_us.query": 3_000,                          # session
    "span_us.parse": 1_000,
    "span_us.bind+optimize": 8_000,
    "span_us.grace.partition": 2_000,
    "span_us.fused.plan": 40_000,                    # programs
    "span_us.fused.result": 10_000,
    "span_us.program.first_call": 2_000_000,
    "span_us.program.dispatch": 50_000,
    "span_us.fused.fetch": 300_000,                  # device
    "span_us.client.wait": 3_990_000,                # wait: in no sum
    "span_us.coordinator.await_fragments": 3_900_000,  # exact beats coordinator.*
    "span_us.dispatch": 3_800_000,
    "span_us.rpc": 10_000,
    "span_us.serving.hbm_hold": 3_950_000,
    "span_us.someone.forgot": 7_000,                 # in no group
}
WANT = {"frontdoor_self_ms": 16.0, "worker_self_ms": 20.0,
        "session_self_ms": 7.0, "programs_host_ms": 1050.0,
        "retrace_ms": 1000.0, "device_wait_ms": 150.0,
        # 4000 ms - (32 + 40 + 14 + 2100 + 300) ms, over 2 queries
        "unattributed_ms": 757.0}


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_without_span_counters(name):
    """A tree whose spans have no counters (the parent of the PR that added
    them) reports nothing, not 0."""
    assert reader(name)(run_of({"jit.miss": 3, "jit.hit": 9})) is None
    assert reader(name)(run_of({})) is None
    assert reader(name)(run_of(SERVED, latencies=())) is None


@pytest.mark.parametrize("name", READERS)
def test_arithmetic(name):
    assert reader(name)(run_of(SERVED)) == pytest.approx(WANT[name])


def test_wait_and_strays_are_in_no_sum():
    sums = {g: span_time.layer_ms(run_of(SERVED), g)
            for g in span_time.groups() if g != span_time.WAIT}
    assert sum(sums.values()) == pytest.approx(
        (32 + 40 + 14 + 2100 + 300) / 2)
    without = {k: v for k, v in SERVED.items()
               if span_time.groups_of(k[len(span_time.PREFIX):])
               not in ([span_time.WAIT], [])}
    for name in READERS:
        assert reader(name)(run_of(without)) == pytest.approx(WANT[name])


def test_a_span_that_never_ran_reads_zero_not_nothing():
    embedded = {k: v for k, v in SERVED.items() if "first_call" not in k}
    assert reader("retrace_ms")(run_of(embedded)) == 0.0
    assert reader("frontdoor_self_ms")(
        run_of({"span_us.query": 5})) == 0.0


def test_unattributed_is_floored():
    """Overlapping fragments can cover more than the client waited."""
    over = dict(SERVED, **{"span_us.fragment.execute": 9_000_000})
    assert reader("unattributed_ms")(run_of(over)) == 0.0


def test_exact_names_beat_patterns():
    assert span_time.groups_of("coordinator.plan") == ["front door"]
    assert span_time.groups_of("coordinator.await_fragments") == ["wait"]
    assert span_time.groups_of("grace.distributed") == ["front door"]
    assert span_time.groups_of("grace.prefetch") == ["session"]
    assert span_time.groups_of("program.first_call") == ["programs"]
    assert span_time.groups_of("fused.fetch") == ["device"]
    assert span_time.groups_of("fused") == []
    assert span_time.groups_of("someone.forgot") == []


def catalog_names() -> list:
    """First column of docs/observability.md's span catalog."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    start = text.index("### Span catalog")
    section = text[start:text.index("\n## ", start)]
    cells = [ln.split("|")[1] for ln in section.splitlines()
             if ln.lstrip().startswith("|") and ln.count("|") >= 2]
    return re.findall(r"`([a-z][a-z0-9_+.*-]*)`", "\n".join(cells))


def test_every_catalogued_span_has_exactly_one_layer():
    """A span added later without a layer fails here: its self time would
    silently read as `unattributed_ms`."""
    names = catalog_names()
    assert len(names) >= 30 and "fused.fetch" in names
    for name in names:
        probe = name.replace("*", "anything")
        assert len(span_time.groups_of(probe)) == 1, name
    # and no name sits in two groups of the file itself
    listed = [n for names in span_time.groups().values() for n in names]
    assert len(listed) == len(set(listed))
