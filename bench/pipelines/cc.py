"""The connected-components pipeline as the benchmark drives it.

``build`` makes the lowering through the program's entry
(``vee.apps.cc_device_lowering``), which puts the graph's dense adjacency
on the device; a job is ``run_device_dag`` on it, the program's own loop
of walks from labels 1..n to the fixed point. ``answer`` is the program's
finalize, the int64 labels; ``Checker`` holds the union-find reference of
the graph; ``work`` counts the least work of one job.

The graph is the deployment's: the configuration's ``graph_seed`` draws
it, as a Graph500 run generates one graph and runs every kernel on it.
The run's seed does not change it, since another graph is another amount
of work: Listing 1 takes 5, 6 or 7 rounds, so as many launches a job, on
the graphs of different seeds.
"""

from __future__ import annotations

from bench.reference import cc as ref


def build(cfg: dict, rows: int, seed: int):
    """The lowering of the configuration's graph; ``rows`` is its vertex count, ``2**scale``."""
    from repro.vee.apps import cc_device_lowering

    if rows != 1 << cfg["scale"]:
        raise ValueError(f"rows={rows} is not 2**scale for scale={cfg['scale']}")
    return cc_device_lowering(cfg["scale"], cfg["edge_factor"], tile=cfg["tile"],
                              tile_c=cfg["tile_c"], seed=cfg["graph_seed"])


def answer(lowering, values: dict):
    """Each vertex's label at the fixed point, int64, in vertex order."""
    return lowering.finalize(values)


class Checker:
    """The graph's exact labels and the number an answer is judged by."""

    def __init__(self, cfg: dict, rows: int, seed: int):
        src, dst = ref.kronecker(cfg["scale"], cfg["edge_factor"], cfg["graph_seed"])
        self.want = ref.components(rows, src, dst)

    def __call__(self, got, request: int = 0) -> dict:
        """The numbers ``got`` is judged by; a job answers one request, 0."""
        return ref.compare(got, self.want)


def control(cfg: dict, rows: int, seed: int):
    """Listing 1 with its labels stored one precision step below the configuration's."""
    return ref.control(cfg["scale"], cfg["edge_factor"], cfg["graph_seed"])


def work(cfg: dict, rows: int, traffic: dict) -> dict:
    """The least work of one job, its one answer: read the stored adjacency once, write n labels.

    The adjacency is ``rows x rows`` float32. A one-pass method
    (union-find over the stored graph) needs no more than one read, so
    the count has no round factor, whatever number of rounds Listing 1
    takes: the walker's share of this roofline is at most 1/rounds, or
    1/(2 rounds) where a launch reads the adjacency twice. The operations
    are counted low, one per label, so the bytes set the bound.
    """
    return {"flops": float(rows), "bytes": 4.0 * rows * rows + 4.0 * rows}
