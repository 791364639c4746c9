"""The /metrics delta and ratio readers on two recorded scrapes (node 1
of a rehearsal of ec42-get-degraded, 5 s apart, cut to the series the
readers use)."""

import json
import os
import types

import pytest
from conftest import BENCH, DATA

from lib import manifest, scrape


@pytest.fixture(scope="module")
def pair():
    with open(os.path.join(DATA, "scrape0.txt")) as f0, \
            open(os.path.join(DATA, "scrape1.txt")) as f1:
        return scrape.parse_metrics(f0.read()), scrape.parse_metrics(f1.read())


def ctx_of(pair, seconds=5.0, method="GET"):
    m0, m1 = pair
    return types.SimpleNamespace(
        scrapes=lambda over: (m0, m1, seconds), primary_method=method)


def read(metric, ctx):
    with open(os.path.join(BENCH, "layer_metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    reader = manifest.load_module(BENCH, "readers", spec["reader"])
    return reader.read(spec.get("params", {}), ctx)


def test_parse_labels_and_totals(pair):
    _, m1 = pair
    assert scrape.total(m1, "feeder_device_items") == 3383
    assert scrape.total(m1, "feeder_device_op_items", {"op": "decode"}) == 3323
    assert scrape.total(m1, "no_such_series") is None  # absent is not 0
    assert {"stage": "d2h"} in scrape.labels_of(m1, "feeder_pipeline_busy_seconds")


def test_delta(pair):
    m0, m1 = pair
    assert scrape.delta(m0, m1, "feeder_device_items") == 3383 - 2351
    assert scrape.delta(m0, m1, "no_such_series") is None
    # a series that is only in the second scrape started from 0
    assert scrape.delta({}, m1, "feeder_device_batches") == 1982


def test_items_per_launch(pair):
    assert read("items_per_launch", ctx_of(pair)) == pytest.approx(
        (3383 - 2351) / (1982 - 1358))


def test_pad_share(pair):
    pad, dev = 259640294 - 178510862, 221721610 - 154084330
    assert read("pad_share", ctx_of(pair)) == pytest.approx(
        100.0 * pad / (pad + dev))


def test_handler_ms_follows_the_primary_method(pair):
    assert read("s3_handler_ms", ctx_of(pair)) == pytest.approx(
        1000.0 * (18.274853 - 13.088996) / (715 - 494))
    # no PUT ended between the two scrapes: nothing to read, left out
    assert read("s3_handler_ms", ctx_of(pair, method="PUT")) is None


def test_stage_busy_is_over_the_seconds_between_scrapes(pair):
    assert read("stage_compute_busy", ctx_of(pair, seconds=5.0)) == \
        pytest.approx(100.0 * (7.190922 - 6.77335) / 5.0)


def test_cache_hit_share_and_compiles(pair):
    assert read("cache_hit_share", ctx_of(pair)) == 0.0
    assert read("compiles_in_window", ctx_of(pair)) == 0.0
    assert read("rpc_ms", ctx_of(pair)) == pytest.approx(
        1000.0 * (154.876597 - 106.265733) / (18366 - 12681))


def test_no_scrapes_reads_nothing():
    ctx = types.SimpleNamespace(scrapes=lambda over: (None, None, None),
                                primary_method="GET")
    assert read("items_per_launch", ctx) is None
