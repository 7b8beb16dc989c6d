"""jit-key fixture twin: quantized / shape-class fingerprints only."""
import jax.numpy as jnp


def round_capacity(n):
    return n


def canonical_direct_table(lo, hi):
    return lo, hi


def batch_proto_key(batch):
    return batch.schema


class Ex:
    def _jitted(self, kind, fp, build):
        return build()

    def sanitized_count(self, batch, build):
        n = batch.num_live()
        want = round_capacity(max(n, 1))
        return self._jitted("compact", ("compact", want), build)

    def prototype_key(self, batch, build):
        fp = ("filter", batch_proto_key(batch), batch.capacity)
        return self._jitted("filter", fp, build)

    def canonical_table(self, bounds, build):
        blo, tsize = canonical_direct_table(int(bounds[0]), int(bounds[1]))
        return self._jitted("join_direct", ("jd", blo, tsize), build)

    def passthrough(self, kind, fingerprint, build):
        # parameters are out of scope for the function-local analysis
        return self._jitted(kind, fingerprint, build)

    def plan_constant(self, plan, batch, build):
        fp = ("limit", plan.limit, plan.offset)
        return self._jitted("limit", fp, build)

    def cast_of_sanitized(self, batch, build):
        want = int(round_capacity(batch.num_live()))
        return self._jitted("compact", ("compact", want), build)


class AdaptiveEx:
    """Adaptive-stats values are fine once quantized through the capacity
    policy, or when they only steer CONTROL FLOW (plan/route choices)."""

    def _jitted(self, kind, fp, build):
        return build()

    def quantized_observation(self, store, fp_key, build):
        rows = store.observed_rows(fp_key)
        want = round_capacity(max(rows or 1, 1))
        return self._jitted("compact", ("compact", want), build)

    def observation_routes_only(self, store, fp_key, build_a, build_b):
        rows = store.observed_rows(fp_key)
        if rows is not None and rows < 1024:
            return self._jitted("small", ("small", 1024), build_a)
        return self._jitted("big", ("big", 4096), build_b)


def shape(e):
    return e


def expr_fingerprint(exprs):
    return tuple(exprs)


class LiteralEx:
    """Expressions enter a key in their shape form; a repr may still label
    a log line or digest a finished key."""

    def _jitted(self, kind, fp, build):
        return build()

    def _push(self, fp, hint_fp="same"):
        pass

    def shape_in_key(self, pred, proto, build):
        fp = ("filter", shape(pred), proto)
        print(repr(fp))
        return self._jitted("filter", fp, build)

    def staged_form(self, exprs, build):
        return self._jitted("project", ("project", expr_fingerprint(exprs)),
                            build)

    def shape_in_pushed_node(self, res):
        self._push(("sort", shape(res)), hint_fp=("sort", shape(res)))
