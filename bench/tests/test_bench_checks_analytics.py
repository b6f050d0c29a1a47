"""A whole run of each analytics cell at a CPU size: sound, it is correct;
with the control in the program's place, or a fault planted underneath the
timed path, ``correct`` comes out false.  (The cells run on one chip, so
there is no exchange between chips to leave out.)"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import reference
from repro.core.pipeline import FrontierPipeline

CELLS = {"g500-bfs-kron20.roots": ("repro.apps.bfs", "BFS_APP"),
         "g500-bfs-kron14-iru.roots": ("repro.apps.bfs", "BFS_APP"),
         "g500-sssp-kron17.roots": ("repro.apps.sssp", "SSSP_APP")}


def patch_run(monkeypatch, fn):
    """``FrontierPipeline.run`` as ``fn(pipe, source, result)``, from the
    window on."""
    orig = FrontierPipeline.run

    def run(self, source=0):
        return fn(self, source, orig(self, source))

    return lambda: monkeypatch.setattr(FrontierPipeline, "run", run)


def unchanged(pipe, source, result):
    # every step hands its state back unchanged: the result is the start
    return pipe.app.result(pipe.init(source)[0])


def altered(pipe, source, result):
    return result.at[source].add(1)


def control(pipe, source, result):
    g = pipe.graph
    kind = "sssp" if pipe.app.name == "sssp" else "bfs"
    return jnp.asarray(reference.control(
        kind, np.asarray(g.row_ptr), np.asarray(g.col_idx),
        np.asarray(g.weights), source))


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    line = tiny.run_tiny(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["programs_lowered_in_window"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("fault", ["unchanged", "altered", "control"])
def test_fault_is_caught(workload, fault, monkeypatch):
    fn = {"unchanged": unchanged, "altered": altered,
          "control": control}[fault]
    line = tiny.run_tiny(workload, seconds=0.2,
                         before_window=patch_run(monkeypatch, fn))
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_half_the_frontier_left_out_is_caught(workload, monkeypatch):
    module, name = CELLS[workload]
    mod = __import__(module, fromlist=[name])
    app = getattr(mod, name)

    def update(state, new_target, graph):
        state, mask = app.update(state, new_target, graph)
        return state, mask & (jnp.arange(mask.shape[0]) % 2 == 0)

    monkeypatch.setattr(mod, name, dataclasses.replace(app, update=update))
    line = tiny.run_tiny(workload, seconds=0.2)
    assert not line["correct"], line["checks"]
