"""A configuration of two tables served by the harness as it serves the
cells of ``BENCHMARK.json``: the toy star under ``data/toy-star`` (its
configuration, generator with ``check_answers``, and a traffic mix of join
queries, all files of its own), run whole on the CPU at its ``small``
size, past the harness's look for a chip."""

import dataclasses

import pytest

import datagen
import records
import reference
import run

CELL, ROOT = next(p.values for p in records.cells() if p.values[1] == records.TOY)
SEED = 2**31 + 5


def run_toy(seconds=2.0):
    bench = {**run.load_benchmark(), "workloads": [CELL]}
    small = datagen.load_config(CELL["config"], ROOT)["small"]
    return run.run_cell(CELL, bench, seed=SEED, seconds=seconds, trace=False,
                        overrides=small, root=ROOT)


@pytest.fixture
def hook_calls(monkeypatch):
    """The join answers each call of the toy's ``check_answers`` was given."""
    calls = []
    load = datagen.load_config

    def loading(name, root=datagen.BENCH):
        cfg = load(name, root)
        hook = cfg["_check_answers"]

        def recorded(tables, sems, answers):
            calls.append(len(answers))
            return hook(tables, sems, answers)

        cfg["_check_answers"] = recorded
        return cfg

    monkeypatch.setattr(datagen, "load_config", loading)
    return calls


def test_toy_star_is_served_as_joins_and_checked_by_its_own_reference(
        hook_calls, monkeypatch):
    from repro.obs import Tracer
    from repro.obs import trace

    # the window's deployment takes the untraced run's tracer: record spans
    tracer = Tracer(capacity=1 << 16)
    monkeypatch.setattr(trace, "NULL_TRACER", tracer)
    result = run_toy()
    assert result["attempted"] > 0 and result["failed"] == 0
    spans = tracer.events()
    misses = [s.span_id for s in spans if s.name == "daisy.execute"]
    joined = {s.parent_id for s in spans if s.name == "execute.join"}
    assert misses and set(misses) <= joined
    assert len(hook_calls) == 1 and hook_calls[0] > 0
    assert result["compared"]["join_pairs_wrong"] == {"value": 0, "limit": 0}
    assert result["correct"] is True, result["compared"]


def test_toy_check_refuses_altered_pairs(hook_calls, monkeypatch):
    """Each join answer pairs its fact rows with the next supplier."""
    from repro.core.executor import Daisy

    execute_join = Daisy._execute_join

    def altered(self, query, plan, report):
        result = execute_join(self, query, plan, report)
        rows = dict(result.join.rows)
        rows[query.joins[0].right] = rows[query.joins[0].right] + 1
        result.join = dataclasses.replace(result.join, rows=rows)
        return result

    monkeypatch.setattr(Daisy, "_execute_join", altered)
    result = run_toy()
    assert result["attempted"] > 0 and result["failed"] == 0
    assert hook_calls and hook_calls[0] > 0
    assert result["compared"]["join_pairs_wrong"]["value"] > 0
    assert result["correct"] is False


def test_join_answers_without_a_reference_stop_the_run(monkeypatch):
    load = datagen.load_config

    def without_hook(name, root=datagen.BENCH):
        cfg = load(name, root)
        del cfg["_check_answers"]
        return cfg

    monkeypatch.setattr(datagen, "load_config", without_hook)
    with pytest.raises(reference.UncheckedAnswers,
                       match="configuration 'toy-star' served .* join answers"):
        run_toy()
