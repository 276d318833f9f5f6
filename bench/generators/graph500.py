"""The ``graph500`` generator: a Graph500 Kronecker edge list sized by the
configuration's ``scale``, ``edge_factor`` and initiator ``A``, ``B``,
``C``.  Its structure comes from the configuration's ``structure_seed``,
its vertex names from the run's seed, within aligned blocks of
``relabel_block`` ids where the configuration names one.
"""

from bench import graph500
from bench.harness import EdgeList


def generate(seed: int, config: dict) -> EdgeList:
    src, dst, n, labels = graph500.generate(
        seed, structure_seed=config["structure_seed"], scale=config["scale"],
        edge_factor=config["edge_factor"], a=config["A"], b=config["B"],
        c=config["C"], relabel_block=config.get("relabel_block"))
    return EdgeList(src=src, dst=dst, n=n, labels=labels,
                    structure_seed=config["structure_seed"])
