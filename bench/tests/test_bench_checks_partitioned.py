"""A whole run of the four-chip cell at a CPU size, on four forced host
devices in a child process (JAX fixes the device count when it starts):
sound, it is correct and lowers nothing in the window; with the exchange
left out, the state unchanged or one depth altered, ``correct`` comes out
false; and the pipeline's counts of the window match a numpy recount on
the tiny graph."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import reference, run

WORKLOAD = "g500-bfs-kron23-part4.roots"
HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = r"""
import json, sys, time
import jax.numpy as jnp
import numpy as np
import tiny
from bench import run
from repro.dist import graph_partition as gp

spec = tiny.spec(sys.argv[1])
cells = []
measured = spec["driver"].Cell.measured


def keep(self):
    cells.append(self)
    return measured(self)


spec["driver"].Cell.measured = keep
orig_run = gp.PartitionedFrontierPipeline.run
orig_exchange = gp._boundary_exchange


def go(before_window=None):
    return run.run_cell(spec, seed=3, seconds=0.5, trace=False,
                        t_start=time.monotonic(), chips=4,
                        before_window=before_window)


def patch_run(fn):
    def patched(self, source=0):
        return fn(self, source, orig_run(self, source))

    def apply():
        gp.PartitionedFrontierPipeline.run = patched
    return apply


def unchanged(pipe, source, result):
    return pipe.gather_result(pipe.init(source)[0])


def altered(pipe, source, result):
    return result.at[source].add(1)


def no_exchange(new_target, ef_buf, **kw):
    # the values the exchange would merge into the owners, left out
    out, ef, live = orig_exchange(new_target, ef_buf, **kw)
    block = kw["block"]
    return jnp.concatenate([new_target[:block], out[block:]]), ef, live


out = {}
line = go()
c = cells[-1]
out["sound"] = {"line": line, "counts": c.counts, "n_parts": spec[
    "config"]["n_parts"], "roots": [int(r) for r, _ in c.results],
    "depths": [d.tolist() for _, d in c.results],
    "row_ptr": c.row_ptr.tolist(), "col_idx": c.col_idx.tolist()}
for name, fn in (("unchanged", unchanged), ("altered", altered)):
    out[name] = go(patch_run(fn))
    gp.PartitionedFrontierPipeline.run = orig_run
# traced with the fault in place from the start
gp._boundary_exchange = no_exchange
out["exchange_left_out"] = go()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(run.ROOT, "src"), run.ROOT, HERE]))
    p = subprocess.run([sys.executable, "-c", CHILD, WORKLOAD], env=env,
                       cwd=run.ROOT, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    (res,) = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(res[len("RESULT "):])


def test_sound_run_is_correct(runs):
    line = runs["sound"]["line"]
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert line["attempted"] > 0 and line["programs_lowered_in_window"] == 0
    assert set(line["metrics"]) == {"teps.bfs", "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["exchange_left_out", "unchanged",
                                   "altered"])
def test_fault_is_caught(runs, fault):
    line = runs[fault]
    assert not line["correct"], line["checks"]


def recount(sound: dict) -> dict:
    """The window's counts from the depths the traversals produced and the
    graph, in numpy: a traversal of deepest level D takes D + 1 supersteps,
    each read once on the host and once more for the two-rung fit test;
    the exchange ships ``P * (P - 1) * lane_cap`` lanes a superstep, and
    a lane is live where a shard's frontier has an edge to the ghost it
    carries (BFS offers ``depth + 1`` on every edge)."""
    rp = np.asarray(sound["row_ptr"])
    col = np.asarray(sound["col_idx"])
    n, p_n = rp.shape[0] - 1, sound["n_parts"]
    block = -(-n // p_n)
    src = np.repeat(np.arange(n), np.diff(rp))
    dst = col[:rp[-1]]
    remote = src // block != dst // block
    # lane_cap: the most ghosts one shard holds of one owner
    pairs = np.unique(np.stack([src[remote] // block, dst[remote]]), axis=1)
    lane_cap = max(np.unique(pairs[0] * p_n + pairs[1] // block,
                             return_counts=True)[1], default=0)
    steps = live = 0
    for depth in map(np.asarray, sound["depths"]):
        deepest = int(depth[depth != reference.UNVISITED].max())
        steps += deepest + 1
        for d in range(deepest + 1):
            edge = remote & (depth[src] == d)
            live += np.unique(np.stack([src[edge] // block, dst[edge]]),
                              axis=1).shape[1]
    return {"supersteps": steps, "host_syncs": 2 * steps,
            "boundary_lanes": steps * p_n * (p_n - 1) * int(lane_cap),
            "boundary_live_lanes": live}


@pytest.mark.parametrize("counter", ["supersteps", "host_syncs",
                                     "boundary_lanes", "boundary_live_lanes"])
def test_counters_match_a_numpy_recount(runs, counter):
    want = recount(runs["sound"])
    assert want["boundary_live_lanes"] > 0
    assert runs["sound"]["counts"][counter] == want[counter]


def test_setup_refuses_a_program_without_partitioned_counts(monkeypatch):
    """A program whose partitioned pipeline has no ``stats()`` fails at the
    top of set-up, before any device work."""
    from repro.dist import graph_partition as gp

    monkeypatch.delattr(gp.PartitionedFrontierPipeline, "stats")
    spec = run.resolve(run.load_benchmark(), WORKLOAD)
    cell = spec["driver"].Cell(spec["config"], spec["mix"], seed=1,
                               seconds=1.0, compiles=None, log=print)
    with pytest.raises(RuntimeError, match="no stats"):
        cell.setup()
