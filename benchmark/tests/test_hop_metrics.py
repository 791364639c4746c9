"""The eight per-layer metrics of the feeder hop and node 1's event
loop (PR 24): each is a data file for the reader `metrics_delta`, and
on two scrapes written out here it gives the value worked out by hand."""

import pytest
from conftest import ROOT

from lib import manifest, scrape

ALL_CELLS = ("ec42-put-mp16", "ec42-get-degraded", "ec42-put-1stream",
             "rep3-put-mp16")
SECONDS = 20.0

# node 1's /metrics as the program renders the new series: a timer is
# <name>_count/_sum/_max per label set, a gauge one line
SCRAPE0 = """\
# TYPE feeder_hop_seconds_count counter
feeder_hop_seconds_count{op="encode_put"} 100
feeder_hop_seconds_sum{op="encode_put"} 1.000000
feeder_hop_seconds_max{op="encode_put"} 0.050000
feeder_hop_seconds_count{op="hash_md5"} 100
feeder_hop_seconds_sum{op="hash_md5"} 0.500000
feeder_hop_seconds_max{op="hash_md5"} 0.040000
feeder_queue_wait_seconds_count{op="encode_put"} 100
feeder_queue_wait_seconds_sum{op="encode_put"} 0.200000
feeder_queue_wait_seconds_count{op="hash_md5"} 100
feeder_queue_wait_seconds_sum{op="hash_md5"} 0.100000
feeder_stage_wait_seconds_count{stage="compute"} 150
feeder_stage_wait_seconds_sum{stage="compute"} 0.030000
feeder_stage_wait_seconds_count{stage="d2h"} 150
feeder_stage_wait_seconds_sum{stage="d2h"} 0.300000
feeder_stage_wait_seconds_count{stage="h2d"} 151
feeder_stage_wait_seconds_sum{stage="h2d"} 0.060000
feeder_resume_lag_seconds_count{hop="compute"} 150
feeder_resume_lag_seconds_sum{hop="compute"} 0.150000
feeder_resume_lag_seconds_count{hop="d2h"} 150
feeder_resume_lag_seconds_sum{hop="d2h"} 0.150000
feeder_resume_lag_seconds_count{hop="h2d"} 151
feeder_resume_lag_seconds_sum{hop="h2d"} 0.150000
feeder_resume_lag_seconds_count{hop="item"} 200
feeder_resume_lag_seconds_sum{hop="item"} 0.100000
api_response_write_seconds_count{api="s3",method="GET"} 10
api_response_write_seconds_sum{api="s3",method="GET"} 5.000000
api_response_write_seconds_count{api="s3",method="PUT"} 40
api_response_write_seconds_sum{api="s3",method="PUT"} 0.004000
api_response_write_seconds_count{api="admin",method="GET"} 3
api_response_write_seconds_sum{api="admin",method="GET"} 0.003000
feeder_pipeline_busy_seconds{stage="h2d"} 2.5
feeder_pipeline_busy_seconds{stage="compute"} 0.5
feeder_pipeline_busy_seconds{stage="d2h"} 4.0
# HELP node_cpu_seconds CPU seconds (user + system) by thread
# TYPE node_cpu_seconds gauge
node_cpu_seconds{thread="all"} 50.000000
node_cpu_seconds{thread="loop"} 30.000000
"""

SCRAPE1 = """\
feeder_hop_seconds_count{op="encode_put"} 300
feeder_hop_seconds_sum{op="encode_put"} 4.000000
feeder_hop_seconds_count{op="hash_md5"} 300
feeder_hop_seconds_sum{op="hash_md5"} 1.500000
feeder_hop_seconds_count{op="decode"} 100
feeder_hop_seconds_sum{op="decode"} 1.000000
feeder_queue_wait_seconds_count{op="encode_put"} 300
feeder_queue_wait_seconds_sum{op="encode_put"} 1.200000
feeder_queue_wait_seconds_count{op="hash_md5"} 300
feeder_queue_wait_seconds_sum{op="hash_md5"} 0.600000
feeder_stage_wait_seconds_count{stage="compute"} 400
feeder_stage_wait_seconds_sum{stage="compute"} 0.080000
feeder_stage_wait_seconds_count{stage="d2h"} 400
feeder_stage_wait_seconds_sum{stage="d2h"} 1.300000
feeder_stage_wait_seconds_count{stage="h2d"} 401
feeder_stage_wait_seconds_sum{stage="h2d"} 0.260000
feeder_resume_lag_seconds_count{hop="compute"} 400
feeder_resume_lag_seconds_sum{hop="compute"} 0.650000
feeder_resume_lag_seconds_count{hop="d2h"} 400
feeder_resume_lag_seconds_sum{hop="d2h"} 0.650000
feeder_resume_lag_seconds_count{hop="h2d"} 401
feeder_resume_lag_seconds_sum{hop="h2d"} 0.650000
feeder_resume_lag_seconds_count{hop="item"} 700
feeder_resume_lag_seconds_sum{hop="item"} 0.351000
api_response_write_seconds_count{api="s3",method="GET"} 60
api_response_write_seconds_sum{api="s3",method="GET"} 40.000000
api_response_write_seconds_count{api="s3",method="PUT"} 40
api_response_write_seconds_sum{api="s3",method="PUT"} 0.004000
api_response_write_seconds_count{api="admin",method="GET"} 5
api_response_write_seconds_sum{api="admin",method="GET"} 0.005000
feeder_pipeline_busy_seconds{stage="h2d"} 5.5
feeder_pipeline_busy_seconds{stage="compute"} 0.9
feeder_pipeline_busy_seconds{stage="d2h"} 9.0
node_cpu_seconds{thread="all"} 86.000000
node_cpu_seconds{thread="loop"} 47.000000
"""

# metric -> (cells that list it, value by hand from the two scrapes)
WANT = {
    # (3.0 + 1.0 + 1.0 s) over (200 + 200 + 100 items); decode is new
    # in the second scrape and started from 0
    "feeder_hop_ms": (ALL_CELLS, 1000.0 * 5.0 / 500),
    "feeder_wait_ms": (ALL_CELLS, 1000.0 * (1.0 + 0.5) / 400),
    # all three stages' waits over the h2d jobs, which are the legs
    "stage_wait_ms": (ALL_CELLS, 1000.0 * (0.05 + 1.0 + 0.2) / 250),
    "stage_h2d_busy": (ALL_CELLS, 100.0 * 3.0 / SECONDS),
    "loop_resume_ms": (ALL_CELLS, 1000.0 * (0.5 * 3 + 0.251) / (250 * 3 + 500)),
    "loop_cpu_share": (ALL_CELLS, 100.0 * 17.0 / SECONDS),
    "node_cpu_cores": (ALL_CELLS, 36.0 / SECONDS),
    # the primary method's responses of the S3 api alone
    "s3_body_ms": (("ec42-get-degraded",), 1000.0 * 35.0 / 50),
}


class Ctx:
    def __init__(self, m0, m1, method):
        self.m0, self.m1, self.primary_method = m0, m1, method

    def scrapes(self, over):
        return self.m0, self.m1, SECONDS


@pytest.fixture(scope="module")
def bench():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def pair():
    return scrape.parse_metrics(SCRAPE0), scrape.parse_metrics(SCRAPE1)


def loaded(bench, cell_name: str, metric: str):
    """The metric as the harness would run it in that cell."""
    cell = manifest.Cell(ROOT, bench, cell_name)
    found = [(m, params, reader) for m, params, reader in cell.per_layer
             if m["name"] == metric]
    return cell, found


@pytest.mark.parametrize("metric", sorted(WANT))
def test_value_by_hand_in_every_cell_that_lists_it(bench, pair, metric):
    cells, want = WANT[metric]
    for name in ALL_CELLS:
        cell, found = loaded(bench, name, metric)
        if name not in cells:
            assert not found, f"{metric} is not {name}'s"
            continue
        (m, params, reader), = found
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["moves"] == "req_p50_ms"
        assert reader.__name__ == "readers.metrics_delta"
        ctx = Ctx(*pair, cell.traffic["primary"]["method"])
        assert reader.read(params, ctx) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_an_absent_series_is_none_not_zero(bench, pair, metric):
    """The parent commit exports none of these series: the metric is
    left out of the line, it is not 0."""
    _, ((_m, params, reader),) = loaded(bench, WANT[metric][0][0], metric)
    mine = {t["series"] for side in ("num", "den")
            for t in params.get(side, []) if isinstance(t, dict)}
    gone = tuple({k: v for k, v in m.items() if k[0] not in mine}
                 for m in pair)
    assert reader.read(params, Ctx(*gone, "GET")) is None
    # present but still (nothing ended between the scrapes): also None
    # for a ratio of counts, 0 for a share of the window's seconds
    still = reader.read(params, Ctx(pair[1], pair[1], "GET"))
    assert still == (0.0 if "seconds" in params["den"] else None)


def test_s3_body_ms_follows_the_primary_method(bench, pair):
    _, ((_m, params, reader),) = loaded(bench, "ec42-get-degraded",
                                        "s3_body_ms")
    # no PUT response was written between the two scrapes
    assert reader.read(params, Ctx(*pair, "PUT")) is None


def test_the_new_entries_are_the_last_eight(bench):
    assert [m["name"] for m in bench["per_layer"][-8:]] == [
        "feeder_hop_ms", "feeder_wait_ms", "stage_wait_ms", "stage_h2d_busy",
        "loop_resume_ms", "loop_cpu_share", "node_cpu_cores", "s3_body_ms"]
