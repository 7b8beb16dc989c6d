"""Shared Flight RPC plumbing for the cluster package.

SECURITY MODEL: the cluster transports are designed for a TRUSTED network.
Control actions (register_table, do_put) accept provider specs naming
filesystem paths, so anyone who can reach the port can read files the process
can. The defaults bind loopback only; before binding a non-loopback host set
IGLOO_TPU_AUTH_TOKEN on every process (coordinator, workers, clients) — all
Flight calls then carry the token in an `x-igloo-token` header and servers
reject calls without it. The token is a shared secret over plaintext gRPC:
it gates access, it is not wire encryption; use a private network or mTLS
termination in front for anything stronger.

FAILURE MODEL: every helper here runs under an `RpcPolicy` — per-call
deadline, bounded connect probe for streams, retry with exponential backoff +
jitter — so a hung peer (TCP accepts, never answers) costs a bounded timeout
instead of a wedged thread, and transient unavailability is retried instead
of failing the query. Classification: `FlightUnavailableError` and timeouts
are RETRYABLE (the peer may come back, or the coordinator will re-dispatch);
`FlightUnauthenticatedError` and `FlightServerError` (a server-side
application error) are FATAL — retrying a query that *failed* would mask
bugs as flakes. Knobs: `IGLOO_RPC_*` env vars or `[rpc]` config
(docs/distributed.md#failure-model). This module is the package's ONLY
Flight connection site — the igloo-lint `rpc-policy` checker flags
`flight.connect` anywhere else, so no code path can bypass the deadlines.

CONNECTIONS: the helpers keep one process-wide pool of idle connections per
peer address (`_ConnPool`). A call checks one out (or opens one), has it to
itself, and checks it back in only after it SUCCEEDED; whatever raised, was
closed early or was abandoned closes its connection, and every retry opens a
new one — so the failure model above reads as it did when each attempt
connected, and a first attempt to a peer just spoken to costs a round trip
and no TCP + HTTP/2 set-up. The auth token rides in `call_options` per call:
a kept connection carries none.

CLOCKS: both ends of every call are timed, by kind — `do_get`, or
`action.<name>` for a name of `protocol`'s action tables. The client end is
`call()`: the `rpc` span of one attempt and, where it closes,
`rpc.calls.<kind>` and `rpc.client_us.<kind>`. The server end is `Served`,
opened on a handler's first line: a `*.serve` span over what the handler
does around its request scope, and `rpc.server_us.<kind>`. Per kind, client
less server is the wire: gRPC, Flight framing, the hand-off between threads.
`DistributedClient` counts its own calls as `client.<kind>`, so that an
operator's admin actions can be told from a query's
(docs/observability.md#transport).
"""
from __future__ import annotations

import atexit
import contextlib
import dataclasses
import json
import os
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Optional

import pyarrow as pa
import pyarrow.flight as flight

from igloo_tpu.cluster import faults
from igloo_tpu.errors import DeadlineExceededError
from igloo_tpu.utils import tracing

AUTH_TOKEN_ENV = "IGLOO_TPU_AUTH_TOKEN"
_HEADER = "x-igloo-token"


def auth_token() -> Optional[str]:
    return os.environ.get(AUTH_TOKEN_ENV) or None


def call_options(timeout_s: Optional[float] = None
                 ) -> Optional[flight.FlightCallOptions]:
    """FlightCallOptions carrying the shared token and/or a gRPC deadline
    (None when neither applies)."""
    kw: dict = {}
    tok = auth_token()
    if tok is not None:
        kw["headers"] = [(_HEADER.encode(), tok.encode())]
    if timeout_s is not None:
        # a deadline already in the past must still produce a DEADLINE_
        # EXCEEDED status, not an invalid-argument error
        kw["timeout"] = max(float(timeout_s), 0.001)
    return flight.FlightCallOptions(**kw) if kw else None


class TokenMiddlewareFactory(flight.ServerMiddlewareFactory):
    """Rejects any call not presenting the shared token."""

    def __init__(self, token: str):
        self._token = token

    def start_call(self, info, headers):
        if info.method == flight.FlightMethod.HANDSHAKE:
            return None  # the auth handler itself validates the handshake
        vals = []
        for k, vs in headers.items():
            key = k.decode() if isinstance(k, bytes) else k
            # handshake-authenticated clients (TokenServerAuthHandler) carry
            # the session token as gRPC call credentials: pyarrow surfaces
            # them as auth-token-bin (or authorization: Bearer <tok>)
            if key.lower() not in (_HEADER, "authorization",
                                   "auth-token-bin"):
                continue
            for v in vs:
                v = v.decode() if isinstance(v, bytes) else v
                if key.lower() == "authorization":
                    v = v.split(" ", 1)[-1]
                vals.append(v)
        if self._token not in vals:
            raise flight.FlightUnauthenticatedError(
                "missing or invalid x-igloo-token (set IGLOO_TPU_AUTH_TOKEN)")
        return None


def server_middleware() -> Optional[dict]:
    """Middleware dict for FlightServerBase when a token is configured."""
    tok = auth_token()
    if tok is None:
        return None
    return {"auth": TokenMiddlewareFactory(tok)}


class TokenServerAuthHandler(flight.ServerAuthHandler):
    """Handshake (reference proto flight.proto:42) wired to the shared
    token: the client's handshake payload must equal the token; the returned
    session token is the same secret (carried by pyarrow on later calls as
    the authorization header). The per-call x-igloo-token middleware stays
    the primary gate — handshake is the protocol-parity path for stock
    clients that use `FlightClient.authenticate`."""

    def __init__(self, token: str):
        super().__init__()
        self._token = token.encode()

    def authenticate(self, outgoing, incoming):
        buf = incoming.read()
        if buf != self._token:
            raise flight.FlightUnauthenticatedError("bad handshake token")
        outgoing.write(self._token)

    def is_valid(self, token):
        if token == self._token:
            return b"igloo"
        # middleware-authenticated calls present no handshake session token
        return b""


class TokenClientAuthHandler(flight.ClientAuthHandler):
    def __init__(self, token: str):
        super().__init__()
        self._token = token.encode()

    def authenticate(self, outgoing, incoming):
        outgoing.write(self._token)
        self._session = incoming.read()

    def get_token(self):
        return self._session


def server_auth_handler() -> Optional[flight.ServerAuthHandler]:
    tok = auth_token()
    return TokenServerAuthHandler(tok) if tok is not None else None


def warn_if_open_bind(host: str, what: str) -> None:
    if host.strip("[]") not in ("127.0.0.1", "localhost", "::1") \
            and auth_token() is None:
        import sys
        print(f"WARNING: {what} binding non-loopback host {host} with NO "
              f"auth token; anyone reaching the port can register tables "
              f"over arbitrary local paths. Set {AUTH_TOKEN_ENV}.",
              file=sys.stderr)


def normalize(addr: str) -> str:
    return addr if "://" in addr else f"grpc+tcp://{addr}"


# --- RPC policy: deadlines, retry, error classification ----------------------


@dataclass(frozen=True)
class RpcPolicy:
    """Failure budget for one RPC: how long each attempt may take, how many
    retryable failures to absorb, and how to back off between them.
    Immutable — derive variants with `with_(...)`."""
    connect_timeout_s: float = 5.0     # stream-open liveness probe bound
    call_timeout_s: float = 120.0      # per-attempt gRPC deadline (actions)
    stream_timeout_s: float = 600.0    # whole-stream gRPC deadline (do_get)
    retries: int = 2                   # retryable-failure budget (attempts-1)
    backoff_base_s: float = 0.05
    backoff_max_s: float = 2.0
    backoff_jitter: float = 0.25       # +-fraction of the backoff step

    def with_(self, **kw) -> "RpcPolicy":
        return dataclasses.replace(self, **kw)

    def backoff_s(self, attempt: int) -> float:
        """Sleep before retry `attempt` (1-based): exponential, capped,
        jittered so a wave of retries against one recovering server spreads
        out instead of stampeding."""
        import random
        base = min(self.backoff_base_s * (2 ** (attempt - 1)),
                   self.backoff_max_s)
        if self.backoff_jitter <= 0:
            return base
        return base * (1.0 + random.uniform(-self.backoff_jitter,
                                            self.backoff_jitter))


_ENV_FIELDS = (("connect_timeout_s", "IGLOO_RPC_CONNECT_TIMEOUT_S"),
               ("call_timeout_s", "IGLOO_RPC_CALL_TIMEOUT_S"),
               ("stream_timeout_s", "IGLOO_RPC_STREAM_TIMEOUT_S"),
               ("retries", "IGLOO_RPC_RETRIES"),
               ("backoff_base_s", "IGLOO_RPC_BACKOFF_BASE_S"),
               ("backoff_max_s", "IGLOO_RPC_BACKOFF_MAX_S"),
               ("backoff_jitter", "IGLOO_RPC_BACKOFF_JITTER"))


def policy_from_env(base: Optional[RpcPolicy] = None) -> RpcPolicy:
    base = base or RpcPolicy()
    kw = {}
    for fld, env in _ENV_FIELDS:
        v = os.environ.get(env)
        if v:
            kw[fld] = int(v) if fld == "retries" else float(v)
    return base.with_(**kw) if kw else base


_default_policy: Optional[RpcPolicy] = None
# the process-wide policy cache is read by every RPC-issuing thread (worker
# heartbeat loops, coordinator dispatch pool, Flight handlers forwarding
# fragments) while config loading may install a policy concurrently — the
# lazy init below would otherwise race and hand two threads different
# policies built from a half-read environment
_policy_lock = threading.Lock()

_GUARDED_BY = {"_policy_lock": ("_default_policy",),
               "_lock": ("_idle",)}     # _ConnPool


def default_policy() -> RpcPolicy:
    global _default_policy
    with _policy_lock:
        if _default_policy is None:
            _default_policy = policy_from_env()
        return _default_policy


def set_default_policy(policy: Optional[RpcPolicy]) -> None:
    """Install a process-wide default (config loading); None re-reads env."""
    global _default_policy
    with _policy_lock:
        _default_policy = policy


def retryable(ex: BaseException) -> bool:
    """Retryable-vs-fatal error classification. Unavailable peers and
    deadline-exceeded attempts may succeed elsewhere or later; auth failures
    and server-side APPLICATION errors (the query itself failed) never will."""
    if isinstance(ex, (flight.FlightUnauthenticatedError,
                       flight.FlightServerError)):
        return False
    if isinstance(ex, (flight.FlightUnavailableError,
                       flight.FlightTimedOutError)):
        return True
    if isinstance(ex, flight.FlightError):
        return False  # internal / cancelled / unknown: do not mask
    return isinstance(ex, (ConnectionError, OSError))


def remaining_s(deadline: Optional[float]) -> Optional[float]:
    """Seconds left until an absolute `time.time()` deadline (None = none)."""
    return None if deadline is None else deadline - time.time()


def check_deadline(deadline: Optional[float], what: str) -> None:
    if deadline is not None and time.time() >= deadline:
        tracing.counter("rpc.deadline_exceeded")
        raise DeadlineExceededError(f"deadline exceeded before {what}")


def _effective_timeout(base: float, deadline: Optional[float]) -> float:
    """Per-attempt gRPC deadline: the policy bound, clamped to whatever is
    left of the caller's absolute deadline."""
    rem = remaining_s(deadline)
    return base if rem is None else max(min(base, rem), 0.001)


def connect(addr: str) -> flight.FlightClient:
    """The package's ONE Flight connection site (gRPC connects lazily; the
    per-call deadline in `call_options` bounds establishment + call). Every
    other module must come through here or the `flight_*` helpers — enforced
    by the igloo-lint `rpc-policy` checker. The caller owns the connection
    (`DistributedClient` keeps its own); the helpers below lease theirs from
    the pool."""
    tracing.counter("rpc.conn_opened")
    return flight.connect(normalize(addr))


def _close_quietly(client: flight.FlightClient) -> None:
    try:
        client.close()
    except Exception:
        pass


class _ConnPool:
    """Idle Flight connections of this process, each under its normalized
    peer address. A connection is made only when its address has none idle
    (a retry drops them first), so an address never holds more than were in
    use at the same time; over all addresses the idle ones are bounded by
    `MAX_IDLE`, least recently returned first out (a test session that
    starts hundreds of servers on ephemeral ports must not keep a
    connection to each dead one)."""

    MAX_IDLE = 64

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: list = []   # (address, connection), oldest return first

    def take(self, addr: str) -> Optional[flight.FlightClient]:
        """The most recently returned idle connection to `addr`, if any."""
        with self._lock:
            for i in range(len(self._idle) - 1, -1, -1):
                if self._idle[i][0] == addr:
                    return self._idle.pop(i)[1]
        return None

    def give(self, addr: str, client: flight.FlightClient) -> None:
        """Keep a connection whose call ran to its end."""
        with self._lock:
            self._idle.append((addr, client))
            evicted = self._idle[:-self.MAX_IDLE]
            del self._idle[:-self.MAX_IDLE]
        for _, c in evicted:
            _close_quietly(c)

    def drop(self, addr: Optional[str] = None) -> None:
        """Close the idle connections to `addr` (None: to every peer)."""
        with self._lock:
            gone = [e for e in self._idle if addr in (None, e[0])]
            self._idle = [e for e in self._idle if addr not in (None, e[0])]
        for _, c in gone:
            _close_quietly(c)

    def idle(self, addr: Optional[str] = None) -> int:
        with self._lock:
            return sum(addr in (None, a) for a, _ in self._idle)


_pool = _ConnPool()
atexit.register(_pool.drop)


def close_idle_connections() -> None:
    """Close every idle pooled connection of this process (a server's
    shutdown; at interpreter exit by `atexit`). A connection in use is its
    caller's, and goes where its call sends it."""
    _pool.drop()


def idle_connections(addr: Optional[str] = None) -> int:
    """How many idle connections the pool holds (to `addr`, or in all)."""
    return _pool.idle(None if addr is None else normalize(addr))


class _Lease:
    """One connection, its holder's alone: an idle one from the pool, else a
    new one. A retry (`fresh`) always opens its own, and first drops what the
    pool holds for that peer — after a failure they are suspects. `release`
    returns the connection to the pool, `discard` closes it; whichever comes
    first wins, so a stream's finally block and its weakref finalizer may
    both run."""

    def __init__(self, addr: str, fresh: bool = False):
        self.addr = normalize(addr)
        self._done = False
        if fresh:
            _pool.drop(self.addr)
        client = None if fresh else _pool.take(self.addr)
        if client is not None:
            tracing.counter("rpc.conn_reused")
        self.client = client or connect(self.addr)

    def release(self) -> None:
        if not self._done:
            self._done = True
            _pool.give(self.addr, self.client)

    def discard(self) -> None:
        if not self._done:
            self._done = True
            _close_quietly(self.client)


# --- both ends of a call get a clock ------------------------------------------


def count_call(what: str, seconds: float) -> None:
    """One call of kind `what` ended at its CLIENT end after `seconds`."""
    tracing.counter(f"rpc.calls.{what}")
    tracing.counter(f"rpc.client_us.{what}", max(round(seconds * 1e6), 0))


@contextlib.contextmanager
def call(what: str, attempt: int = 0):
    """The client end of one RPC attempt: the `rpc` span (attrs `what`, the
    retry ordinal — retries and backoff against a flaky peer show on the
    stitched trace; with no trace to record into the span still has its
    profiler event and its counters) and, where it closes, the call counted
    under its kind. A call nested in it (the probe inside a stream's open)
    is counted under its own kind and left out of this one's time, as a
    child is left out of a span's self time: the kinds add up."""
    with tracing.span("rpc", what=what, attempt=attempt) as sp:
        try:
            yield sp
        finally:
            count_call(what, sp.elapsed_s - sum(
                c.end - c.start for c in sp.children if c.end))


def action_kind(name: str, table: dict) -> str:
    """`action.<name>` for a name of the serving side's action table: the
    kinds are a closed set, so a peer's misspelt action makes no counter."""
    return f"action.{name}" if name in table else "action.unknown"


class Served:
    """The server end of one Flight call: `with Served(span, what):` on the
    handler's first line. Its body is a span `span` (attr `what`); a request
    scope opened inside becomes its child (`tracing.note_child`), so the
    span's self time is what the handler does around the scope — decode,
    parse, encode, an action without a scope whole. Where the block ends,
    its duration goes to `rpc.server_us.<what>`, the twin of the caller's
    `rpc.client_us.<what>`; a handler that hands Flight a batch generator
    passes it through `stream`, and the clock runs on to the generator's
    exhaustion or close."""

    __slots__ = ("span", "what", "t0", "t_body", "_cm", "_streams")

    def __init__(self, span: str, what: str):
        self.span = span
        self.what = what
        self._streams = False

    def __enter__(self) -> "Served":
        self.t0 = time.perf_counter()
        self._cm = tracing.span(self.span, what=self.what)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        self._cm.__exit__(*exc)
        self.t_body = time.perf_counter()
        if not self._streams or exc[0] is not None:
            self._count(self.t_body)
        return False

    def _count(self, t1: float) -> None:
        tracing.counter(f"rpc.server_us.{self.what}",
                        max(round((t1 - self.t0) * 1e6), 0))

    def stream(self, gen, own: bool):
        """`gen`, with this call's clock running to its end. `own`: no other
        span covers the serving of the stream, so the time from the
        handler's return to the stream's end is `span` self time too,
        recorded by its bounds (the generator runs on Flight's thread
        between the handler's return and the call's end; a thread-local span
        cannot stay open across that). A stream Flight never starts counts
        nothing."""
        self._streams = True

        def timed():
            try:
                yield from gen
            finally:
                t1 = time.perf_counter()
                self._count(t1)
                if own:
                    tracing.close_span(self.span, None, t1 - self.t_body)
        return timed()


def _run_attempts(addr: str, what: str, fn, policy: Optional[RpcPolicy],
                  deadline: Optional[float]):
    """The ONE retry loop: lease a connection per attempt (the first from
    the pool, every retry a new one), run `fn(lease)`, classify-then-retry
    with backoff, never past the caller's deadline. An attempt that raises
    discards its connection; one that returns leaves its lease to `fn`'s
    caller, who releases it (an action: at once; a stream: when exhausted).
    Every attempt is a `call`: a failed one and its retry are two calls of
    one kind."""
    policy = policy or default_policy()
    attempt = 0
    while True:
        check_deadline(deadline, what)
        lease = None
        ok = False
        try:
            with call(what, attempt):
                faults.inject(f"client.{what}")
                lease = _Lease(addr, fresh=attempt > 0)
                out = fn(lease)
            ok = True
            return out
        except Exception as ex:
            if isinstance(ex, flight.FlightTimedOutError):
                tracing.counter("rpc.timeouts")
            if attempt >= policy.retries or not retryable(ex):
                raise
            attempt += 1
            tracing.counter("rpc.retries")
            delay = policy.backoff_s(attempt)
            rem = remaining_s(deadline)
            if rem is not None and rem <= delay:
                # sleeping would burn the rest of the budget and the next
                # loop's check_deadline would mask THIS error with a generic
                # DeadlineExceededError — surface the real failure now
                raise
            time.sleep(delay)
        finally:
            if lease is not None and not ok:
                lease.discard()


def _with_retry(addr: str, what: str, fn, policy: Optional[RpcPolicy],
                deadline: Optional[float],
                timeout_s: Optional[float] = None):
    """Run `fn(client, options)` under the policy: per-attempt deadline
    (recomputed each attempt as the caller's absolute deadline shrinks),
    classify-then-retry with backoff. The attempt that returns gives its
    connection back to the pool."""
    policy = policy or default_policy()

    def attempt(lease):
        t = _effective_timeout(timeout_s or policy.call_timeout_s, deadline)
        out = fn(lease.client, call_options(timeout_s=t))
        lease.release()
        return out
    return _run_attempts(addr, what, attempt, policy, deadline)


def flight_action(addr: str, name: str, payload: Optional[dict] = None,
                  policy: Optional[RpcPolicy] = None,
                  deadline: Optional[float] = None,
                  timeout_s: Optional[float] = None) -> dict:
    """One action RPC on a pooled connection — under the RPC policy
    (per-call deadline, retry/backoff on retryable failures). Returns the
    decoded first result (or {}). `deadline` is an absolute `time.time()`
    bound the whole call (retries included) must respect."""
    body = flight_action_raw(addr, name, payload, policy=policy,
                             deadline=deadline, timeout_s=timeout_s)
    return json.loads(body) if body else {}


def flight_action_raw(addr: str, name: str,
                      payload: Optional[dict] = None,
                      policy: Optional[RpcPolicy] = None,
                      deadline: Optional[float] = None,
                      timeout_s: Optional[float] = None) -> bytes:
    """One action RPC returning the raw first-result bytes — for
    actions whose payload is NOT JSON (the `metrics` Prometheus text)."""
    body = json.dumps(payload).encode() if payload is not None else b""

    def call(client, options):
        results = list(client.do_action(flight.Action(name, body), options))
        return results[0].body.to_pybytes() if results else b""
    return _with_retry(addr, f"action.{name}", call, policy, deadline,
                       timeout_s)


def flight_actions_raw(addr: str, actions,
                       policy: Optional[RpcPolicy] = None):
    """Run several action RPCs over ONE connection, yielding each action's
    raw first-result bytes in order. `actions` iterates (name, payload)
    pairs. The connection goes back to the pool when the generator is
    exhausted; a call that raises, or a generator closed early, closes it —
    the worker's registration pre-warm pulls hundreds of compile-cache
    entries over it. Each call carries the policy's per-call deadline but is
    NOT retried (callers — the compile-cache push/pull loops — already have
    per-entry retry logic, and replaying the already-consumed prefix of
    `actions` is impossible)."""
    policy = policy or default_policy()
    lease = _Lease(addr)
    try:
        for name, payload in actions:
            with call(f"action.{name}"):
                faults.inject(f"client.action.{name}")
                body = json.dumps(payload).encode() \
                    if payload is not None else b""
                results = list(lease.client.do_action(
                    flight.Action(name, body),
                    call_options(timeout_s=policy.call_timeout_s)))
            yield results[0].body.to_pybytes() if results else b""
        lease.release()
    finally:
        lease.discard()


def flight_stream_response(schema, gen):
    """Server-side half of a streaming do_get. Two stream shapes, because
    pyarrow makes each wrong in a different way:

    - GeneratorStream(schema, gen) preserves Flight error STATUSES raised
      mid-generator (a FlightUnavailableError stays UNAVAILABLE on the wire,
      which the client-side peer-loss classification depends on) — but its
      IPC writer never emits dictionary batches, so any dictionary-bearing
      schema dies at the peer's reader with "expected number (1) of
      dictionaries at the start of the stream".
    - A RecordBatchReader-backed RecordBatchStream writes dictionary batches
      correctly and still pulls one batch at a time (spilled fragments
      stream straight off their IPC spill files) — but a mid-generator
      exception crosses the C++ reader boundary and degrades to a generic
      FlightServerError.

    So: reader-backed only when the schema actually carries dictionaries
    (encoded exchange slices), GeneratorStream everywhere else."""
    if any(pa.types.is_dictionary(f.type) for f in schema):
        return flight.RecordBatchStream(
            pa.RecordBatchReader.from_batches(schema, gen))
    return flight.GeneratorStream(schema, gen)


def flight_stream_batches(addr: str, ticket,
                          policy: Optional[RpcPolicy] = None,
                          deadline: Optional[float] = None):
    """Streaming do_get: returns (schema, record-batch generator). The
    connection stays open until the generator is exhausted (or closed), so
    the consumer holds at most one in-flight batch instead of the whole
    result — the data-plane half of the fragment tier's streaming transfers.
    `ticket` may be str or bytes (bucketed exchange tickets are JSON).

    Failure model: the OPEN (probe + do_get + schema) retries under the
    policy; the stream itself runs under a gRPC deadline of
    `stream_timeout_s` clamped to the caller's `deadline` and is never
    retried mid-flight (the consumer re-fetches from scratch — batches
    already yielded cannot be un-consumed). A bounded `ping` probe
    (connect_timeout_s) catches a HUNG peer at open time; without it a
    worker that accepts TCP but never answers would hold do_get for the
    full stream timeout (on a kept connection the probe is a round trip; it
    is a call of its own kind, `action.ping`, nested in the open's `rpc`).
    The open's `rpc` span ends with the schema, but `rpc.client_us.do_get`
    runs on to the end of the batch generator, so that it bounds the serving
    side's `rpc.server_us.do_get` from above.
    The connection returns to the pool only when the generator is EXHAUSTED;
    a stream that raises, is closed early, or is ABANDONED (the weakref
    finalizer: a never-started generator's close() does not run its finally
    block) closes it — a half-read stream never goes back."""
    raw = ticket if isinstance(ticket, bytes) else ticket.encode()
    policy = policy or default_policy()

    def open_stream(lease):
        c = lease.client
        probe_t = _effective_timeout(policy.connect_timeout_s, deadline)
        with call("action.ping"):
            list(c.do_action(flight.Action("ping", b""),
                             call_options(timeout_s=probe_t)))
        t = _effective_timeout(policy.stream_timeout_s, deadline)
        reader = c.do_get(flight.Ticket(raw), call_options(timeout_s=t))
        # the schema read is where a hung/failed do_get actually surfaces —
        # it must happen inside the retried attempt
        return lease, reader, reader.schema

    lease, reader, schema = _run_attempts(addr, "do_get", open_stream,
                                          policy, deadline)
    t_open = time.perf_counter()

    def gen():
        try:
            for chunk in reader:
                if chunk.data is not None:
                    yield chunk.data
            lease.release()
        finally:
            lease.discard()
            tracing.counter("rpc.client_us.do_get",
                            round((time.perf_counter() - t_open) * 1e6))
    g = gen()
    weakref.finalize(g, lease.discard)
    return schema, g
