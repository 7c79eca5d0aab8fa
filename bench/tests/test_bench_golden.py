"""The plain reference and the accounting identities give, bit for bit, what
they gave before each workload kind moved into a module of its own
(``bench/kinds``). The digests in ``data/reference_digests.json`` were taken
with the reference as it stood before the move; each is the sha256 of
``json.dumps(payload, sort_keys=True)``."""
from __future__ import annotations

import copy
import hashlib
import json

import pytest

from bench_tiny import BENCH, SEED, sharded_tiny, tiny
from yardstick import check, reference

DIGESTS = json.loads((BENCH / "tests" / "data" / "reference_digests.json").read_text())


def digest(payload) -> str:
    return hashlib.sha256(json.dumps(payload, sort_keys=True).encode()).hexdigest()


def _records(out: dict) -> list:
    """``reference.simulate``'s answer with its tuple keys as lists."""
    return sorted([list(k), v] for k, v in out.items())


def _simulated(cell) -> list:
    return _records(reference.simulate(cell.config, cell.grid(), cell.unit_seed(SEED, 0)))


def _fabricated_identities() -> list:
    """``check.identities`` on answers made from the tiny DLRM's reference:
    sound, one count off by one, one configuration missing, one batch too
    many, a failed unit, and all of them together."""
    cell = tiny()
    grid = cell.grid()
    seeds = [cell.unit_seed(SEED, i) for i in range(2)]
    sound = [reference.simulate(cell.config, grid, s) for s in seeds]
    keys = sorted(sound[0])
    off = copy.deepcopy(sound[0])
    off[keys[1]]["batches"][1]["cache_hits"] += 1
    missing = copy.deepcopy(sound[0])
    del missing[keys[2]]
    extra = copy.deepcopy(sound[1])
    extra[keys[3]]["batches"].append(extra[keys[3]]["batches"][0])
    units = [(sound[0], seeds[0]), (off, seeds[0]), (missing, seeds[0]),
             (extra, seeds[1]), ({}, seeds[1])]
    each = [check.identities(cell.config, grid, [u], [s]) for u, s in units]
    together = check.identities(cell.config, grid, [u for u, _ in units],
                                [s for _, s in units])
    return [each, together]


PAYLOADS = {
    "dlrm_tiny": lambda: _simulated(tiny()),
    "sharded_tiny": lambda: _simulated(sharded_tiny()),
    "cmdr_plus_b4x2": lambda: _simulated(
        tiny("cmdr_plus.decode_grid24", batch_size=4, num_batches=2)),
    "identities": _fabricated_identities,
}


@pytest.mark.parametrize("name", sorted(PAYLOADS))
def test_matches_digest_from_before_the_move(name):
    assert digest(PAYLOADS[name]()) == DIGESTS[name]["sha256"]
