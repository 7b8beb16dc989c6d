"""`datagen_spec_keys.py`'s tables with `O_COMMENT` as TPC-H's own text.

Clause 4.2.2.10: a "text string [min, max]" is a substring of a 300 MB pool
of pseudo-text, of a length drawn uniformly in [min, max], at an offset
drawn uniformly; O_COMMENT is text string [19, 78] (clause 4.2.3), so each
order has its own comment, ~48.5 characters on average, and at SF10 the 15 M
comments are nearly all distinct. The pool follows the grammar of clause
4.2.2.14 with the word and production weights of dbgen's `dists.dss`:

    sentence  N V T | N V P T | N V N T | N P V N T | N P V P T   (3,3,3,1,1)
    N (noun phrase)   noun | adj noun | adj, adj noun | adv adj noun
    V (verb phrase)   verb | aux verb | verb adv | aux verb adv
    P (prep. phrase)  preposition "the" N
    T (terminator)    . ; : ? ! --   (attached to the word before it)

`datagen.py` draws every comment from a pool of 50,000 strings of 2-6 words
out of 27, so a predicate over a comment column meets 50,000 values where
the spec's data has one a row.

`O_CUSTKEY` is uniform over the customer keys that are not multiples of 3
(clause 4.2.3); `datagen.py` moves a multiple of 3 to the key below it, so
keys of the form 3k + 2 place twice the orders of keys 3k + 1, which halves
and doubles the counts of orders per customer that TPC-H Q13 reports.

Every other column is `datagen_spec_keys`', value for value; the pool and
these two columns draw from generators of their own, seeded from
(seed, what), so no other column moves.
"""
from __future__ import annotations

import functools

import numpy as np
import pyarrow as pa

import datagen
import datagen_spec_keys

TABLES = datagen_spec_keys.TABLES
#: dbgen's TEXT_POOL_SIZE
POOL_BYTES = 300 * 1024 * 1024
#: O_COMMENT is text string [19, 78] (clause 4.2.3)
COMMENT_LEN = {"o_comment": (19, 78)}

# (word, weight), dbgen's dists.dss
_NOUNS = [("packages", 40), ("requests", 40), ("accounts", 40),
          ("deposits", 40), ("foxes", 20), ("ideas", 20),
          ("theodolites", 20), ("pinto beans", 20), ("instructions", 20),
          ("dependencies", 10), ("excuses", 10), ("platelets", 10),
          ("asymptotes", 10), ("courts", 5), ("dolphins", 5)] + [
    (w, 1) for w in (
        "multipliers", "sauternes", "warthogs", "frets", "dinos",
        "attainments", "somas", "Tiresias", "patterns", "forges", "braids",
        "frays", "warhorses", "dugouts", "notornis", "epitaphs", "pearls",
        "tithes", "waters", "orbits", "gifts", "sheaves", "depths",
        "sentiments", "decoys", "realms", "pains", "grouches", "escapades",
        "hockey players")]
_VERBS = [("sleep", 20), ("wake", 20), ("are", 20), ("cajole", 20),
          ("haggle", 20), ("nag", 10), ("use", 10), ("boost", 10),
          ("affix", 5), ("detect", 5), ("integrate", 5)] + [
    (w, 1) for w in (
        "maintain", "nod", "was", "lose", "sublate", "solve", "thrash",
        "promise", "engage", "hinder", "print", "x-ray", "breach", "eat",
        "grow", "impress", "mold", "poach", "serve", "run", "dazzle",
        "snooze", "doze", "unwind", "kindle", "play", "hang", "believe",
        "doubt")]
_ADJECTIVES = [("special", 20), ("pending", 20), ("unusual", 20),
               ("express", 20)] + [
    (w, 1) for w in (
        "furious", "sly", "careful", "blithe", "quick", "fluffy", "slow",
        "quiet", "ruthless", "thin", "close", "dogged", "daring", "brave",
        "stealthy", "permanent", "enticing", "idle", "busy")] + [
    ("regular", 50), ("final", 40), ("ironic", 40), ("even", 30),
    ("bold", 20), ("silent", 10)]
_ADVERBS = [("sometimes", 1), ("always", 1), ("never", 1),
            ("furiously", 50), ("slyly", 50), ("carefully", 50),
            ("blithely", 40), ("quickly", 30), ("fluffily", 20)] + [
    (w, 1) for w in (
        "slowly", "quietly", "ruthlessly", "thinly", "closely", "doggedly",
        "daringly", "bravely", "stealthily", "permanently", "enticingly",
        "idly", "busily", "regularly", "finally", "ironically", "evenly",
        "boldly", "silently")]
_PREPOSITIONS = [("about", 50), ("above", 50), ("according to", 50),
                 ("across", 50), ("after", 50), ("against", 40),
                 ("along", 40), ("alongside of", 30), ("among", 30),
                 ("around", 20), ("at", 10)] + [
    (w, 1) for w in (
        "atop", "before", "behind", "beneath", "beside", "besides",
        "between", "beyond", "by", "despite", "during", "except", "for",
        "from", "in place of", "inside", "instead of", "into", "near", "of",
        "on", "outside", "over", "past", "since", "through", "throughout",
        "to", "toward", "under", "until", "up", "upon", "without", "with",
        "within")]
_AUXILIARIES = [(w, 1) for w in (
    "do", "may", "might", "shall", "will", "would", "can", "could", "should",
    "ought to", "must", "will have to", "shall have to", "could have to",
    "should have to", "must have to", "need to", "try to")]
_TERMINATORS = [(".", 50), (";", 1), (":", 1), ("?", 1), ("!", 1),
                ("--", 1)]
_THE = [("the", 1)]
#: (sentence form, weight): N V T, N V P T, N V N T, N P V N T, N P V P T
#: as (leading P?, tail: 0 none, 1 N, 2 P)
_SENTENCES = [((0, 0), 3), ((0, 2), 3), ((0, 1), 3), ((1, 1), 1),
              ((1, 2), 1)]
_NP = [(0, 10), (1, 20), (2, 10), (3, 50)]   # N, J N, J, J N, D J N
_VP = [(0, 30), (1, 1), (2, 40), (3, 1)]     # V, X V, V D, X V D

# every word of every list, one id each; a token is id * 3 + separator
_LISTS = (_NOUNS, _VERBS, _ADJECTIVES, _ADVERBS, _PREPOSITIONS, _AUXILIARIES,
          _TERMINATORS, _THE)
_WORDS = [w for lst in _LISTS for w, _ in lst]
_FIRST = np.cumsum([0] + [len(lst) for lst in _LISTS])[:-1]
(_NOUN, _VERB, _ADJ, _ADV, _PREP, _AUX, _TERM, _THE_ID) = _FIRST
_SEPS = (" ", ", ", "")      # after a word, after a listed adjective, before T


def _token_table():
    """(bytes [n_tokens, width] uint8, lengths [n_tokens]) of every
    (word, separator) pair, by token id."""
    tokens = [(w + s).encode() for w in _WORDS for s in _SEPS]
    width = max(len(t) for t in tokens)
    table = np.zeros((len(tokens), width), dtype=np.uint8)
    for i, t in enumerate(tokens):
        table[i, :len(t)] = np.frombuffer(t, dtype=np.uint8)
    return table, np.array([len(t) for t in tokens], dtype=np.int64)


def _draw(rng, weighted, n: int) -> np.ndarray:
    """Indices into `weighted` ((item, weight) pairs), n of them, by weight:
    each index repeated its weight's times, one uniform draw a row."""
    ids = np.repeat(np.arange(len(weighted)), [w for _, w in weighted])
    return ids[rng.integers(0, len(ids), n)]


def _noun_phrases(rng, n: int) -> np.ndarray:
    """[n, 3] word ids (-1: no word), the separators still to apply."""
    form = np.array([f for f, _ in _NP])[_draw(rng, _NP, n)]
    out = np.full((n, 3), -1, dtype=np.int64)
    out[:, 2] = (_NOUN + _draw(rng, _NOUNS, n)) * 3
    adj = (_ADJ + _draw(rng, _ADJECTIVES, n)) * 3
    out[:, 1] = np.where(form > 0, adj, -1)
    first = np.where(form == 2, (_ADJ + _draw(rng, _ADJECTIVES, n)) * 3 + 1,
                     (_ADV + _draw(rng, _ADVERBS, n)) * 3)
    out[:, 0] = np.where(form >= 2, first, -1)
    return out


def _prep_phrases(rng, n: int) -> np.ndarray:
    """[n, 5]: preposition, "the", noun phrase."""
    return np.concatenate([
        ((_PREP + _draw(rng, _PREPOSITIONS, n)) * 3)[:, None],
        np.full((n, 1), _THE_ID * 3), _noun_phrases(rng, n)], axis=1)


def _verb_phrases(rng, n: int) -> np.ndarray:
    """[n, 3]: auxiliary, verb, adverb."""
    form = np.array([f for f, _ in _VP])[_draw(rng, _VP, n)]
    aux = (_AUX + _draw(rng, _AUXILIARIES, n)) * 3
    adv = (_ADV + _draw(rng, _ADVERBS, n)) * 3
    return np.stack([np.where(form % 2 == 1, aux, -1),
                     (_VERB + _draw(rng, _VERBS, n)) * 3,
                     np.where(form >= 2, adv, -1)], axis=1)


def _sentences(rng, n: int) -> np.ndarray:
    """The tokens of n sentences, in order: an int64 stream of token ids."""
    lead, tail = np.array([f for f, _ in _SENTENCES]).T[
        :, _draw(rng, _SENTENCES, n)]
    none = np.full((n, 5), -1, dtype=np.int64)
    tail_np = np.concatenate([np.full((n, 2), -1), _noun_phrases(rng, n)],
                             axis=1)
    slots = np.concatenate([
        _noun_phrases(rng, n),
        np.where(lead[:, None] == 1, _prep_phrases(rng, n), none),
        _verb_phrases(rng, n),
        np.where(tail[:, None] == 1, tail_np,
                 np.where(tail[:, None] == 2, _prep_phrases(rng, n), none)),
        ((_TERM + _draw(rng, _TERMINATORS, n)) * 3)[:, None]], axis=1)
    stream = slots[slots >= 0]
    # a terminator follows its word without a space
    before = np.flatnonzero(stream // 3 >= _TERM)
    before = before[(before > 0) & (stream[before] // 3 < _THE_ID)] - 1
    stream[before] = stream[before] - stream[before] % 3 + 2
    return stream


@functools.lru_cache(maxsize=1)
def text_pool(seed: int, size: int = POOL_BYTES) -> np.ndarray:
    """`size` bytes (uint8) of the grammar's text, from (seed, "text pool");
    the last one is kept (~9 s to draw 300 MB)."""
    rng = datagen._rng(seed, "text pool")
    table, lengths = _token_table()
    out, have = [], 0
    while have < size:
        tokens = _sentences(rng, 1 << 20)
        rows = table[tokens]
        text = rows[np.arange(table.shape[1]) < lengths[tokens][:, None]]
        out.append(text)
        have += len(text)
    return np.concatenate(out)[:size]


def text_strings(rng, pool: np.ndarray, n: int, lo: int, hi: int) -> pa.Array:
    """n substrings of `pool`, lengths uniform in [lo, hi], offsets uniform
    in [0, len(pool) - hi] (dbgen's `dbg_text`), as an Arrow string array.
    Built as string views into the pool (16 B a row: length, the first four
    bytes, buffer 0, offset; every length is over the 12 bytes a view holds
    inline) and copied out by Arrow's cast: no Python string a row."""
    assert 12 < lo <= hi and len(pool) < 2 ** 31
    views = np.zeros((n, 4), dtype=np.int32)
    views[:, 0] = rng.integers(lo, hi + 1, n)
    views[:, 3] = rng.integers(0, len(pool) - hi + 1, n)
    views[:, 1] = pool[views[:, 3, None] + np.arange(4)].view(np.int32)[:, 0]
    return pa.Array.from_buffers(
        pa.string_view(), n,
        [None, pa.py_buffer(views), pa.py_buffer(pool)]).cast(pa.string())


def customer_keys(rng, n_customers: int, n: int) -> np.ndarray:
    """n keys uniform over [1, n_customers] less the multiples of 3: the
    k-th such key is 3 * (k // 2) + 1 + k % 2."""
    k = rng.integers(0, n_customers - n_customers // 3, n)
    return 3 * (k // 2) + 1 + k % 2


def gen_tables(sf: float, seed: int, tables=TABLES) -> dict:
    """{name: Arrow table} as datagen_spec_keys.gen_tables gives it, each
    text column of COMMENT_LEN drawn from the spec's text pool and
    `o_custkey` uniform over the keys that place orders."""
    out = datagen_spec_keys.gen_tables(sf=sf, seed=seed, tables=tables)
    n_customers = datagen._counts(sf)["customer"]
    pool = None
    for name, tbl in out.items():
        for col in tbl.column_names:
            if col in COMMENT_LEN:
                if pool is None:
                    pool = text_pool(seed)
                lo, hi = COMMENT_LEN[col]
                values = text_strings(datagen._rng(seed, col), pool,
                                      tbl.num_rows, lo, hi)
            elif col == "o_custkey":
                values = pa.array(customer_keys(datagen._rng(seed, col),
                                                n_customers, tbl.num_rows),
                                  type=pa.int64())
            else:
                continue
            tbl = tbl.set_column(tbl.column_names.index(col), col, values)
        out[name] = tbl
    return out
