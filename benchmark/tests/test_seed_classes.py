"""``seed_classes`` of the ``solo`` family of drivers: run seeds sorted into
classes of like work, the classes taken in turn at the file's own ratio, each
in an order ``--seed`` shuffles; without it every run's seed is drawn fresh."""

import importlib.util
import os
import random

import pytest

import run as bench

SPEC = bench.load_json(bench.ROOT, "BENCHMARK.json")
_spec = importlib.util.spec_from_file_location(
    "bench_drivers_solo_t", os.path.join(bench.HERE, "drivers", "solo.py"))
solo = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(solo)


def seeds_of(workload, seed, n, traffic=None):
    ctx = bench.make_ctx(SPEC, workload, seed, False, on_chip=False)
    ctx["traffic"].update(traffic or {})
    driver = bench.load_module("drivers", ctx["traffic"]["driver"]).Driver(ctx)
    try:
        return [driver._seed() for _ in range(n)]
    finally:
        driver.close()


@pytest.mark.parametrize("sizes", ((18, 30), (1, 1), (5, 1), (7, 11, 2),
                                   (3, 3, 3), (48,)))
def test_every_stretch_holds_each_class_share(sizes):
    classes = {str(i): [100 * i + j for j in range(n)]
               for i, n in enumerate(sizes)}
    total = sum(sizes)
    order = solo.class_order(classes, random.Random(5))
    got = [next(order) for _ in range(3 * total + 7)]
    of = {s: k for k, v in classes.items() for s in v}
    # any prefix: each class's share to within one run
    for k in range(1, len(got) + 1):
        for name, n in zip(classes, sizes):
            have = sum(1 for s in got[:k] if of[s] == name)
            assert abs(have - n * k / total) < 1, (k, name)
    # any stretch (a window starts after set-up's two runs): within two
    for a in range(0, total):
        for b in range(a + 1, len(got) + 1):
            for name, n in zip(classes, sizes):
                have = sum(1 for s in got[a:b] if of[s] == name)
                assert abs(have - n * (b - a) / total) < 2
    # once round: every seed once; then the same order again
    assert sorted(got[:total]) == sorted(of)
    assert got[total:2 * total] == got[:total]


def test_mesh4_runs_its_classes_in_turn_in_an_order_of_the_seed():
    spec = bench.load_json(bench.HERE, "traffic", "mesh_solo.json")[
        "seed_classes"]
    of = {s: k for k, v in spec["classes"].items() for s in v}
    k = len(of)
    assert k == sum(len(v) for v in spec["classes"].values())  # no seed twice
    a = seeds_of("paxos10k.mesh4", 2_147_483_659, k + 5)
    b = seeds_of("paxos10k.mesh4", 7, k + 5)
    assert sorted(a[:k]) == sorted(b[:k]) == sorted(of)
    assert a[:k] != b[:k]  # the order inside a class is the seed's
    assert [of[s] for s in a] == [of[s] for s in b]  # the turn of classes not
    assert a[k:] == a[:5] and b[k:] == b[:5]  # round and round
    assert a == seeds_of("paxos10k.mesh4", 2_147_483_659, k + 5)
    # a window half or twice as long holds the same share of every class
    for name, seeds in spec["classes"].items():
        for n in (12, 24, 47, 95):
            have = sum(1 for s in seeds_of("paxos10k.mesh4", 11, n + 2)[2:]
                       if of[s] == name)
            assert abs(have - len(seeds) / k * n) < 2


def test_without_classes_every_run_draws_a_fresh_seed():
    a = seeds_of("pbft100k.solo", 2_147_483_659, 40)
    b = seeds_of("pbft100k.solo", 7, 40)
    assert len(set(a)) == 40 and not set(a) & set(b)
    # the same driver with classes in its traffic file
    c = seeds_of("pbft100k.solo", 7, 8, {"seed_classes": {
        "by": "view_changes", "classes": {"0": [1, 2, 3], "1": [4]}}})
    assert c[:4] == c[4:] and sorted(c[:4]) == [1, 2, 3, 4]
    # one class and no key to sort by: the same seeds in the seed's order
    d = seeds_of("pbft100k.solo", 7, 6, {"seed_classes": {
        "classes": {"all": [1, 2, 3]}}})
    assert d[:3] == d[3:] and sorted(d[:3]) == [1, 2, 3]


def test_misses_are_the_runs_out_of_their_class():
    traffic = {"seed_classes": {"by": "retries",
                                "classes": {"3": [1, 2], "6": [3]}}}
    samples = [{"seed": 1, "row": {"retries": 3}},
               {"seed": 2, "row": {"retries": 6}},
               {"seed": 3, "row": {"retries": 6}},
               {"seed": 9, "row": {"retries": 3}}]
    assert solo.seed_class_misses(traffic, samples) == 2
