"""jit-key fixture: raw data-dependent ints reaching _jitted fingerprints."""
import jax
import jax.numpy as jnp


class Ex:
    def _jitted(self, kind, fp, build):
        return build()

    def inline_source(self, batch, build):
        return self._jitted("compact", ("compact", batch.num_live()), build)  # BAD

    def tainted_name(self, batch, build):
        n = int(jnp.sum(batch.live))
        fp = ("agg", n)
        return self._jitted("agg", fp, build)  # BAD

    def via_device_get(self, dev, build):
        total = jax.device_get(dev)
        key = ("join", int(total))
        return self._jitted("join", key, build)  # BAD

    def arithmetic_wrap(self, batch, build):
        n = batch.num_live()
        cap = max(n, 1) * 2
        return self._jitted("sort", ("sort", cap), build)  # BAD

    def suppressed(self, batch, build):
        # justified one-off: documented rationale would go here
        return self._jitted("x", ("x", batch.num_live()), build)  # lint: allow(jit-key)


class AdaptiveEx:
    """Adaptive-stats accessors are taint sources: observed cardinalities
    must never reach a _jitted fingerprint unquantized."""

    def _jitted(self, kind, fp, build):
        return build()

    def observed_rows_in_key(self, store, fp_key, build):
        rows = store.observed_rows(fp_key)
        return self._jitted("probe", ("probe", rows), build)  # BAD

    def observed_record_in_key(self, store, fp_key, build):
        rec = store.observed(fp_key)
        cap = max(rec["rows"], 1) * 2
        return self._jitted("agg", ("agg", cap), build)  # BAD

    def selectivity_in_key(self, store, fp_key, build):
        sel = store.selectivity(fp_key)
        return self._jitted("join", ("join", sel), build)  # BAD


class LiteralEx:
    """A literal's VALUE in a program key is one program per parameter set:
    `repr` and `fingerprint` print it."""

    def _jitted(self, kind, fp, build):
        return build()

    def _push(self, fp, hint_fp="same"):
        pass

    def repr_in_key(self, pred, proto, build):
        fp = ("filter", repr(pred), proto)
        return self._jitted("filter", fp, build)  # BAD

    def fingerprint_in_key(self, exprs, build):
        return self._jitted("project", ("project", fingerprint(exprs)), build)  # BAD

    def repr_in_pushed_node(self, res):
        self._push(("sort", tuple(repr(e) for e in res)))  # BAD

    def repr_in_hint_member(self, res, core):
        self._push(core, ("join", repr(res)))  # BAD
