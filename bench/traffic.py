"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix is data only.  It names the loop (``closed``: each session submits
its next query when the previous one is answered), the number of sessions,
and a list of query templates with their shares.  A template holds

* ``slots``: each slot is a list of ``choices`` (each choice a list of
  ``[column, op, value]`` predicates), or a ``domain`` (``"column"``: the
  predicate ``column == v`` for every ``v`` in ``0..size-1``, the size read
  from the configuration's ``domains``) with its ``op``;
* ``draw``: ``{"joint": {"zipf": s}}`` draws one rank over the product of
  the slots' choices (first slot outermost), ranks in listed order;
  otherwise each slot draws by its own ``draw`` (``{"zipf": s}`` or
  ``{"uniform": true}``);
* ``groupby`` (``keys``, ``agg``, ``value``) and ``project``, as the query
  returns them;
* ``table``: the table the query reads (default: the configuration's only
  or first table), and ``joins``: ``{"right", "left_on", "right_on"}`` per
  joined table, in join order.

A column of a joined table is written ``"table.column"``: a predicate so
written becomes that join's ``right_preds``.  The keys and the value of
``groupby`` may be qualified the same way; when they all name one table
the query's group-by names it (``GroupBySpec.table``), and when they span
tables they are passed as written, for the program to resolve.  A name
without a known table before its dot is a column of the query's table.
Names are resolved once, when the mix is read, so a drawn ``QuerySpec``
already says which table each name belongs to.

Every session draws from its own stream of the run's seed, so a seed fixes
each session's sequence of queries whatever the timing of the run.  Ranks
map to parameters in the order the file lists them.

Draws are stratified, so that every seed sends the same mix and not only
the same law: each draw (a template, a slot, a joint rank) is taken in
blocks of ``STRATA`` draws, one from each of the block's equal-probability
strata of its law, in an order the seed shuffles.  A rank whose
probability is at least ``1/STRATA`` therefore comes up the same number of
times, give or take one, in every block of every seed; rarer ranks are
spread evenly over their quantiles, and the seed picks which rank within
each stratum.  Seeds then differ in the order of the queries and in the
tail's ranks, not in how many of each kind they send.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from datagen import zipf_weights

BENCH = Path(__file__).resolve().parent

# draws per stratified block (see the module docstring)
STRATA = 64


Pred = Tuple[str, str, object]


@dataclasses.dataclass(frozen=True)
class Join:
    right: str
    left_on: str
    right_on: str
    preds: Tuple[Pred, ...] = ()  # on the right table, unqualified


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One generated query, in the benchmark's own terms, with every name
    given its table (see the module docstring): what the program is asked
    (``run.build_query``) and what the reference judges."""

    template: str
    preds: Tuple[Pred, ...]  # on ``table``
    groupby: Optional[Tuple[Tuple[str, ...], str, Optional[str]]] = None
    project: Tuple[str, ...] = ()
    table: Optional[str] = None  # the mix sets it: the template's or the default
    joins: Tuple[Join, ...] = ()
    groupby_table: Optional[str] = None  # where every group-by name is on one


def load_mix(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _slot_choices(slot: dict, domains: Dict[str, int]) -> List[List[list]]:
    if "choices" in slot:
        return slot["choices"]
    col = slot["domain"]
    return [[[col, slot.get("op", "=="), v]] for v in range(domains[col])]


class Strata:
    """Indices ``0..len(p)-1`` drawn with probabilities ``p``, stratified:
    each block of ``block`` draws takes one from each stratum
    ``[k/block, (k+1)/block)`` of the cumulative law, at a point the
    generator picks, and is handed out in an order it shuffles."""

    def __init__(self, p: np.ndarray, r: np.random.Generator, block: int = STRATA):
        self.cdf = np.cumsum(p)
        self.cdf /= self.cdf[-1]
        self.r, self.block, self.buf = r, block, []

    def __next__(self) -> int:
        if not self.buf:
            u = (np.arange(self.block) + self.r.random(self.block)) / self.block
            idx = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                             len(self.cdf) - 1)
            self.r.shuffle(idx)
            self.buf = idx.tolist()
        return self.buf.pop()


def _weights(draw: dict, n: int) -> np.ndarray:
    if "zipf" in draw:
        return zipf_weights(n, float(draw["zipf"]))
    if draw.get("uniform"):
        return np.full(n, 1.0 / n)
    raise ValueError(f"unknown draw {draw!r}")


class _Template:
    """One template with its names resolved once, against its own tables
    (``default_table`` where it names none): a draw only picks choices."""

    def __init__(self, spec: dict, domains: Dict[str, int], default_table: str):
        self.name = spec["name"]
        self.share = float(spec["share"])
        self.table = spec.get("table") or default_table
        self.joins = tuple(
            (j["right"], j["left_on"], j["right_on"]) for j in spec.get("joins", ())
        )
        known = {self.table, *(r for r, _, _ in self.joins)}
        if len(known) != 1 + len(self.joins):
            raise ValueError(f"{self.name}: a table is joined twice")

        def split(name: str):
            """(table, column) of a name; table None where it names none."""
            head, dot, col = name.partition(".")
            return (head, col) if dot and head in known else (None, name)

        def pred(col, op, value):
            table, col = split(col)
            return table or self.table, (col, op, value)

        # each choice as (table, predicate) pairs
        self.choices = [
            [tuple(pred(*p) for p in choice) for choice in _slot_choices(s, domains)]
            for s in spec["slots"]
        ]
        sizes = [len(c) for c in self.choices]
        joint = spec.get("draw", {}).get("joint")
        self.sizes = sizes
        self.joint = None if joint is None else _weights(joint, int(np.prod(sizes)))
        self.slot_w = [
            None if joint is not None else _weights(s["draw"], n)
            for s, n in zip(spec["slots"], sizes)
        ]
        gb = spec.get("groupby")
        self.groupby, self.groupby_table = None, None
        if gb is not None:
            keys, value = tuple(gb["keys"]), gb.get("value")
            self.groupby = (keys, gb.get("agg", "count"), value)
            names = [split(n) for n in keys + ((value,) if value else ())]
            named = {t or self.table for t, _ in names}
            if any(t for t, _ in names) and len(named) == 1:
                self.groupby_table = named.pop()
                cols = [c for _, c in names]
                self.groupby = (tuple(cols[:len(keys)]), self.groupby[1],
                                cols[len(keys)] if value else None)
        self.project = tuple(spec.get("project", ()))

    def drawers(self, r: np.random.Generator):
        """One session's stratified drawers for this template's ranks."""
        if self.joint is not None:
            return [Strata(self.joint, r)]
        return [Strata(w, r) for w in self.slot_w]

    def draw(self, drawers) -> QuerySpec:
        if self.joint is not None:
            idx = np.unravel_index(next(drawers[0]), self.sizes)
        else:
            idx = [next(d) for d in drawers]
        preds = {self.table: [], **{r: [] for r, _, _ in self.joins}}
        for choices, i in zip(self.choices, idx):
            for table, pred in choices[int(i)]:
                preds[table].append(pred)
        return QuerySpec(
            self.name, tuple(preds[self.table]), self.groupby, self.project,
            self.table,
            tuple(Join(r, lo, ro, tuple(preds[r])) for r, lo, ro in self.joins),
            self.groupby_table,
        )


class Mix:
    """A traffic mix bound to a configuration's domains and the table a
    template reads where it names none."""

    def __init__(self, spec: dict, domains: Dict[str, int], default_table: str):
        if spec.get("loop") != "closed":
            raise ValueError("only closed-loop mixes are generated")
        self.sessions = int(spec["sessions"])
        self.templates = [_Template(t, domains, default_table)
                          for t in spec["templates"]]
        shares = np.array([t.share for t in self.templates])
        self.shares = shares / shares.sum()

    def session_stream(self, seed_rng_entropy: List[int]) -> Iterator[QuerySpec]:
        """Endless query sequence of one session."""
        r = np.random.default_rng(seed_rng_entropy)
        pick = Strata(self.shares, r)
        drawers = [t.drawers(r) for t in self.templates]
        while True:
            i = next(pick)
            yield self.templates[i].draw(drawers[i])
