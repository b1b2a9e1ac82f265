"""The fleet as the seed deals it: which rack each node is in. The
server process builds the nodes from it and the submitter reads the
spread's counts from it, so neither takes it from the program."""
from __future__ import annotations

import random


def node_id(i: int) -> str:
    return f"node-{i:06d}"


def rack_of(i_rack: int) -> str:
    return f"rack-{i_rack:03d}"


def racks(fleet: dict, seed: int) -> dict:
    """node id -> rack, every rack the same size to within one node."""
    n_nodes, n_racks = int(fleet["nodes"]), int(fleet["racks"])
    deal = [i % n_racks for i in range(n_nodes)]
    random.Random(seed).shuffle(deal)
    return {node_id(i): rack_of(r) for i, r in enumerate(deal)}
