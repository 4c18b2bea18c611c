"""Live-observability subsystem tests (ISSUE 4).

Covers the exposition parser, the per-worker HTTP endpoint, the hvdrun
driver aggregator (merge + summary line), the metrics-port preflight, the
2-rank endpoint smoke test (tier-1), the 4-rank compressed acceptance run,
and the process-mode stall-inspector regression (warning text + ``stalled``
gauge) the core.cpp stall path never had.
"""

import json
import os
import socket

import pytest

from conftest import assert_all_ok, launch_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")

SAMPLE = """\
# HELP hvdtpu_ops_total Completed collective ops
# TYPE hvdtpu_ops_total counter
hvdtpu_ops_total{op="ALLREDUCE"} 7
hvdtpu_ops_total{op="ALLGATHER"} 2
# HELP hvdtpu_cycle_seconds tick latency
# TYPE hvdtpu_cycle_seconds histogram
hvdtpu_cycle_seconds_bucket{le="0.0001"} 5
hvdtpu_cycle_seconds_bucket{le="+Inf"} 9
hvdtpu_cycle_seconds_sum 0.25
hvdtpu_cycle_seconds_count 9
# HELP hvdtpu_stalled gauge doc
# TYPE hvdtpu_stalled gauge
hvdtpu_stalled 0
"""


def _free_port_block(n: int) -> int:
    """A base port with n consecutive free ports above it."""
    for _ in range(50):
        s = socket.socket()
        s.bind(("", 0))
        base = s.getsockname()[1]
        s.close()
        if base + n >= 65535:
            continue
        ok = True
        for off in range(n + 1):
            probe = socket.socket()
            try:
                probe.bind(("", base + off))
            except OSError:
                ok = False
                break
            finally:
                probe.close()
        if ok:
            return base
    raise RuntimeError("no free port block found")


class TestExpositionParser:
    def test_parse_families_and_samples(self):
        from horovod_tpu.observability import (parse_prometheus_text,
                                               sample_value)
        parsed = parse_prometheus_text(SAMPLE)
        assert parsed["hvdtpu_ops_total"]["type"] == "counter"
        assert sample_value(parsed, "hvdtpu_ops_total", op="ALLREDUCE") == 7
        assert sample_value(parsed, "hvdtpu_ops_total", op="ALLGATHER") == 2
        # Histogram children attach to the base family with their suffix.
        hist = parsed["hvdtpu_cycle_seconds"]
        assert hist["type"] == "histogram"
        assert sample_value(parsed, "hvdtpu_cycle_seconds", suffix="count") \
            == 9
        assert sample_value(parsed, "hvdtpu_cycle_seconds", suffix="bucket",
                            le="+Inf") == 9
        assert sample_value(parsed, "hvdtpu_stalled") == 0

    def test_malformed_line_raises(self):
        from horovod_tpu.observability import parse_prometheus_text
        with pytest.raises(ValueError):
            parse_prometheus_text("not a metric line at all!")

    def test_label_escapes_roundtrip(self):
        from horovod_tpu.observability import parse_prometheus_text
        parsed = parse_prometheus_text(
            'esc_total{name="a\\"b\\\\c"} 1\n')
        (_suf, labels, value), = parsed["esc_total"]["samples"]
        assert labels == {"name": 'a"b\\c'} and value == 1.0

    def test_escaped_label_values_exhaustive(self):
        """ISSUE 12 satellite: every escape the exposition format defines
        (\\n, \\", \\\\) plus a literal '}' inside a value — the greedy
        label-block regex must not truncate at the embedded brace."""
        from horovod_tpu.observability import parse_prometheus_text
        parsed = parse_prometheus_text(
            'esc_total{a="line1\\nline2",b="br{ace}s",c="tail\\\\"} 2\n')
        (_suf, labels, value), = parsed["esc_total"]["samples"]
        assert labels == {"a": "line1\nline2", "b": "br{ace}s",
                          "c": "tail\\"}
        assert value == 2.0

    def test_inf_bucket_roundtrip_through_relabel(self):
        """ISSUE 12 satellite: the +Inf bucket must survive parse ->
        relabel -> reparse with its `le` intact and still resolve through
        sample_value — the aggregator's quantile math keys on it."""
        from horovod_tpu.observability import (parse_prometheus_text,
                                               sample_value)
        from horovod_tpu.runner.metrics_agg import relabel_with_rank
        relabeled = relabel_with_rank(SAMPLE, 3)
        assert 'hvdtpu_cycle_seconds_bucket{le="+Inf",rank="3"} 9' \
            in relabeled
        parsed = parse_prometheus_text(relabeled)
        assert sample_value(parsed, "hvdtpu_cycle_seconds", suffix="bucket",
                            le="+Inf", rank="3") == 9
        # The finite bucket kept its bound too (no float re-rendering).
        assert sample_value(parsed, "hvdtpu_cycle_seconds", suffix="bucket",
                            le="0.0001", rank="3") == 5

    def test_render_special_values(self):
        """NaN and ±Inf are legal exposition values (promtool parity):
        re-rendering must emit them, not crash on int(NaN)."""
        import math

        from horovod_tpu.observability import (parse_prometheus_text,
                                               render_exposition)
        text = ("# TYPE odd gauge\nodd NaN\n"
                "# TYPE pos gauge\npos +Inf\n"
                "# TYPE neg gauge\nneg -Inf\n")
        rendered = render_exposition(parse_prometheus_text(text))
        assert "odd NaN" in rendered
        assert "pos +Inf" in rendered and "neg -Inf" in rendered
        reparsed = parse_prometheus_text(rendered)
        assert math.isnan(reparsed["odd"]["samples"][0][2])


class TestHistogramQuantile:
    """ISSUE 12 satellite: the merged-histogram quantile helper's edge
    cases — empty, zero-count, and single-bucket histograms."""

    @staticmethod
    def _parse(text):
        from horovod_tpu.observability import parse_prometheus_text
        return parse_prometheus_text(text)

    def test_empty_inputs(self):
        from horovod_tpu.runner.metrics_agg import histogram_quantile
        assert histogram_quantile({}, "hvdtpu_recovery_seconds", 0.5) is None
        # Parsed dumps without the family, and with the family but no
        # bucket samples, both report "no data" instead of crashing.
        assert histogram_quantile(
            {0: self._parse("# TYPE x counter\nx 1\n")}, "h", 0.5) is None
        assert histogram_quantile(
            {0: self._parse("# TYPE h histogram\nh_sum 0\nh_count 0\n")},
            "h", 0.5) is None

    def test_zero_count_histogram(self):
        from horovod_tpu.runner.metrics_agg import histogram_quantile
        parsed = self._parse(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 0\nh_bucket{le="+Inf"} 0\n'
            "h_sum 0\nh_count 0\n")
        assert histogram_quantile({0: parsed}, "h", 0.5) is None

    def test_single_inf_bucket_has_no_bound_info(self):
        """A lone +Inf bucket holds a count but no bound — the helper used
        to interpolate from an implicit 0.0 and report p50=0 for a
        histogram whose observations could be anything."""
        from horovod_tpu.runner.metrics_agg import histogram_quantile
        parsed = self._parse(
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 7\nh_sum 3.5\nh_count 7\n')
        assert histogram_quantile({0: parsed}, "h", 0.5) is None

    def test_single_finite_bucket_interpolates(self):
        from horovod_tpu.runner.metrics_agg import histogram_quantile
        parsed = self._parse(
            "# TYPE h histogram\n"
            'h_bucket{le="2"} 4\nh_bucket{le="+Inf"} 4\n'
            "h_sum 4\nh_count 4\n")
        # All mass in [0, 2]: the median interpolates to the middle.
        assert histogram_quantile({0: parsed}, "h", 0.5) \
            == pytest.approx(1.0)

    def test_merges_bucket_counts_across_ranks(self):
        from horovod_tpu.runner.metrics_agg import histogram_quantile
        a = self._parse("# TYPE h histogram\n"
                        'h_bucket{le="1"} 10\nh_bucket{le="2"} 10\n'
                        'h_bucket{le="+Inf"} 10\n')
        b = self._parse("# TYPE h histogram\n"
                        'h_bucket{le="1"} 0\nh_bucket{le="2"} 10\n'
                        'h_bucket{le="+Inf"} 10\n')
        # 20 observations total: 10 under 1, 10 in (1, 2]; p75 lands
        # halfway through the second bucket.
        assert histogram_quantile({0: a, 1: b}, "h", 0.75) \
            == pytest.approx(1.5)
        # Observations above every finite bound: the finite edge is the
        # best lower bound the data supports.
        c = self._parse("# TYPE h histogram\n"
                        'h_bucket{le="1"} 1\nh_bucket{le="+Inf"} 9\n')
        assert histogram_quantile({0: c}, "h", 0.99) == 1.0


class TestMetricsServer:
    def test_serve_and_scrape(self):
        from horovod_tpu.observability import MetricsServer, scrape
        server = MetricsServer(dump_fn=lambda: SAMPLE, port=0,
                               health={"rank": 3, "size": 8})
        server.start()
        try:
            text = scrape("127.0.0.1", server.port)
            assert 'hvdtpu_ops_total{op="ALLREDUCE"} 7' in text
            health = json.loads(
                scrape("127.0.0.1", server.port, "/healthz"))
            assert health == {"rank": 3, "size": 8, "status": "ok"}
            import urllib.error
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", server.port, "/other")
            assert e.value.code == 404
        finally:
            server.stop()

    def test_dump_error_does_not_kill_endpoint(self):
        from horovod_tpu.observability import MetricsServer, scrape
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("registry hiccup")
            return SAMPLE

        server = MetricsServer(dump_fn=flaky, port=0)
        server.start()
        try:
            import urllib.error
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", server.port)
            assert e.value.code == 500
            assert "hvdtpu_ops_total" in scrape("127.0.0.1", server.port)
        finally:
            server.stop()


class TestAggregator:
    def test_relabel_and_merge(self):
        from horovod_tpu.runner.metrics_agg import merge_dumps
        merged = merge_dumps({0: SAMPLE, 1: SAMPLE})
        assert 'hvdtpu_ops_total{op="ALLREDUCE",rank="0"} 7' in merged
        assert 'hvdtpu_ops_total{op="ALLREDUCE",rank="1"} 7' in merged
        assert 'hvdtpu_stalled{rank="1"} 0' in merged
        # Meta lines deduplicated.
        assert merged.count("# TYPE hvdtpu_ops_total counter") == 1
        # Family grouping: ALL ranks' samples of a family sit contiguously
        # under its single header (the exposition format forbids
        # interleaving families; strict consumers reject it).
        lines = merged.splitlines()
        seg = lines[lines.index("# TYPE hvdtpu_ops_total counter"):
                    lines.index("# HELP hvdtpu_cycle_seconds tick latency")]
        assert 'hvdtpu_ops_total{op="ALLREDUCE",rank="0"} 7' in seg
        assert 'hvdtpu_ops_total{op="ALLREDUCE",rank="1"} 7' in seg
        # Still valid exposition after relabeling.
        from horovod_tpu.observability import parse_prometheus_text
        parsed = parse_prometheus_text(merged)
        assert len(parsed["hvdtpu_ops_total"]["samples"]) == 4

    def test_summary_reliability_and_zerocopy_counters(self):
        """The one-line summary carries the PR-6/PR-7 counters it predated:
        cumulative failure detections, recovery p50 from the merged
        histogram, and the zero-copy engagement rate (ISSUE 10 satellite)."""
        from horovod_tpu.observability import parse_prometheus_text
        from horovod_tpu.runner.metrics_agg import (histogram_quantile,
                                                    summarize)

        quiet = parse_prometheus_text(
            "# TYPE hvdtpu_ops_total counter\n"
            'hvdtpu_ops_total{op="ALLREDUCE"} 5\n')
        line, _ = summarize({0: quiet}, None, 0.0)
        assert "failures=0" in line
        assert "zc=off" in line
        assert "recovery_p50" not in line  # no observations yet

        busy = parse_prometheus_text(
            "# TYPE hvdtpu_failures_detected_total counter\n"
            "hvdtpu_failures_detected_total 2\n"
            "# TYPE hvdtpu_zerocopy_sends_total counter\n"
            "hvdtpu_zerocopy_sends_total 30\n"
            "# TYPE hvdtpu_zerocopy_fallbacks_total counter\n"
            "hvdtpu_zerocopy_fallbacks_total 10\n"
            "# TYPE hvdtpu_recovery_seconds histogram\n"
            'hvdtpu_recovery_seconds_bucket{le="0.1"} 0\n'
            'hvdtpu_recovery_seconds_bucket{le="0.4"} 2\n'
            'hvdtpu_recovery_seconds_bucket{le="+Inf"} 2\n'
            "hvdtpu_recovery_seconds_sum 0.5\n"
            "hvdtpu_recovery_seconds_count 2\n")
        line, _ = summarize({0: busy, 1: quiet}, None, 0.0)
        assert "failures=2" in line
        assert "zc=75%(30zc/10cp)" in line
        assert "recovery_p50=" in line
        # Interpolated p50 inside the (0.1, 0.4] bucket: both observations
        # land there, target = 1 of 2 -> 0.1 + 0.5 * 0.3 = 0.25.
        p50 = histogram_quantile({0: busy}, "hvdtpu_recovery_seconds", 0.5)
        assert abs(p50 - 0.25) < 1e-9, p50

    def test_scrape_merge_and_summary(self):
        from horovod_tpu.observability import MetricsServer
        from horovod_tpu.runner.metrics_agg import MetricsAggregator

        stalled = SAMPLE.replace("hvdtpu_stalled 0", "hvdtpu_stalled 1")
        stalled += ("# TYPE hvdtpu_allreduce_raw_bytes_total counter\n"
                    "hvdtpu_allreduce_raw_bytes_total 4000\n"
                    "# TYPE hvdtpu_allreduce_wire_bytes_total counter\n"
                    "hvdtpu_allreduce_wire_bytes_total 1000\n")
        servers = [MetricsServer(dump_fn=lambda: SAMPLE, port=0),
                   MetricsServer(dump_fn=lambda: stalled, port=0)]
        for s in servers:
            s.start()
        agg = MetricsAggregator(
            {0: ("127.0.0.1", servers[0].port),
             1: ("127.0.0.1", servers[1].port)},
            port=0, print_summary=False)
        try:
            dumps = agg.scrape_once()
            assert sorted(dumps) == [0, 1]
            assert 'rank="1"' in agg.merged()
            line = agg.summary_line(dumps)
            assert line.startswith("hvdrun metrics:")
            assert "wire_ratio=4.00x" in line
            assert "stalled=[1]" in line
            # Second pass: op-rate delta becomes available (0 here).
            line2 = agg.summary_line(agg.scrape_once())
            assert "ops/s=0.0" in line2
            # The aggregator's own HTTP endpoint serves the merged view.
            agg._server.start()
            from horovod_tpu.observability import scrape
            assert 'rank="0"' in scrape("127.0.0.1", agg.port)
        finally:
            agg._server.stop()
            for s in servers:
                s.stop()

    def test_rate_ignores_ranks_missing_from_a_round(self):
        """A worker whose scrape failed one round must not dent the ops/s
        delta then spike it when it returns — rates difference per-rank
        counters only over ranks present in both snapshots."""
        from horovod_tpu.observability import parse_prometheus_text
        from horovod_tpu.runner.metrics_agg import summarize

        def parsed(ops):
            return parse_prometheus_text(
                "# TYPE hvdtpu_ops_total counter\n"
                f'hvdtpu_ops_total{{op="ALLREDUCE"}} {ops}\n')

        _line, prev = summarize({0: parsed(1000), 1: parsed(1000)},
                                None, 0.0)
        # Rank 1's scrape fails this round; rank 0 advanced by 100.
        line, prev = summarize({0: parsed(1100)}, prev, 10.0)
        assert "ops/s=10.0" in line, line
        # Rank 1 returns at 1200 — it was absent from prev, so only rank
        # 0's +100 counts (no 200-op spike from rank 1's two rounds).
        line, _prev = summarize({0: parsed(1200), 1: parsed(1200)},
                                prev, 20.0)
        assert "ops/s=10.0" in line, line

    def test_unreachable_worker_skipped(self):
        from horovod_tpu.runner.metrics_agg import MetricsAggregator
        from conftest import free_port
        agg = MetricsAggregator({0: ("127.0.0.1", free_port())}, port=0,
                                print_summary=False)
        try:
            assert agg.scrape_once() == {}
            assert agg.merged() == ""
            assert agg.unreachable() == [0]
        finally:
            agg._server.stop()

    def test_killed_worker_flagged_not_fatal(self):
        """ISSUE 13 satellite: a worker dying mid-scrape is skipped AND
        named in the summary line; the reachable ranks' cycle survives."""
        from horovod_tpu.observability import MetricsServer
        from horovod_tpu.runner.metrics_agg import MetricsAggregator

        servers = [MetricsServer(dump_fn=lambda: SAMPLE, port=0)
                   for _ in range(2)]
        for s in servers:
            s.start()
        agg = MetricsAggregator(
            {0: ("127.0.0.1", servers[0].port),
             1: ("127.0.0.1", servers[1].port)},
            port=0, print_summary=False)
        try:
            dumps = agg.scrape_once()
            assert sorted(dumps) == [0, 1] and agg.unreachable() == []
            line = agg.summary_line(dumps)
            assert "unreachable" not in line
            # Rank 1 dies (endpoint gone, connection refused).
            servers[1].stop()
            dumps = agg.scrape_once()
            assert sorted(dumps) == [0]
            assert agg.unreachable() == [1]
            line = agg.summary_line(dumps)
            assert line.startswith("hvdrun metrics:")
            assert "unreachable=[1]" in line
            # The merged view keeps serving the survivor.
            assert 'rank="0"' in agg.merged()
            assert 'rank="1"' not in agg.merged()
        finally:
            agg._server.stop()
            servers[0].stop()

    def test_elastic_replacement_endpoint_update(self):
        """ISSUE 13 satellite: elastic re-rendezvous replaces a dead
        worker's endpoint; update_endpoints() swaps the target live and
        the replacement is scraped on the next round without a restart."""
        from horovod_tpu.observability import MetricsServer
        from horovod_tpu.runner.metrics_agg import MetricsAggregator
        from conftest import free_port

        alive = MetricsServer(dump_fn=lambda: SAMPLE, port=0)
        alive.start()
        replacement = MetricsServer(dump_fn=lambda: SAMPLE, port=0)
        replacement.start()
        agg = MetricsAggregator(
            {0: ("127.0.0.1", alive.port),
             1: ("127.0.0.1", free_port())},  # dead slot
            port=0, print_summary=False)
        try:
            dumps = agg.scrape_once()
            assert sorted(dumps) == [0] and agg.unreachable() == [1]
            agg.update_endpoints({0: ("127.0.0.1", alive.port),
                                  1: ("127.0.0.1", replacement.port)})
            dumps = agg.scrape_once()
            assert sorted(dumps) == [0, 1]
            assert agg.unreachable() == []
        finally:
            agg._server.stop()
            alive.stop()
            replacement.stop()

    def test_truncated_dump_flagged_not_fatal(self):
        """A worker dying MID-RESPONSE hands the aggregator a malformed
        exposition: the rank is flagged, the cycle completes."""
        from horovod_tpu.runner.metrics_agg import MetricsAggregator
        agg = MetricsAggregator({}, port=0, print_summary=False)
        try:
            line = agg.summary_line({0: SAMPLE, 1: "hvdtpu_{oops 1 2 3"})
            assert line.startswith("hvdrun metrics:")
            assert "unreachable=[1]" in line
        finally:
            agg._server.stop()


class TestMetricsPortPreflight:
    def test_busy_port_named(self):
        from horovod_tpu.runner.preflight import check_metrics_ports
        base = _free_port_block(3)
        blocker = socket.socket()
        blocker.bind(("", base + 1))  # rank 1's port
        try:
            with pytest.raises(RuntimeError) as e:
                check_metrics_ports(["localhost", "localhost"], base,
                                    aggregator_port=base + 2)
            msg = str(e.value)
            assert f"port {base + 1}" in msg and "rank 1" in msg
            assert "HVDTPU_METRICS_PORT" in msg
        finally:
            blocker.close()

    def test_all_free_passes(self):
        from horovod_tpu.runner.preflight import check_metrics_ports
        base = _free_port_block(3)
        check_metrics_ports(["localhost", "localhost"], base,
                            aggregator_port=base + 2)

    def test_remote_hosts_not_probed(self):
        # Remote slots cannot be bind-probed from the driver; the check
        # must not fail on them (the worker itself fails fast at init).
        from horovod_tpu.runner.preflight import check_metrics_ports
        base = _free_port_block(2)
        blocker = socket.socket()
        blocker.bind(("", base))
        try:
            check_metrics_ports(["remote-host-a"], base)
        finally:
            blocker.close()

    def test_endpoint_helper(self):
        from horovod_tpu.observability import worker_metrics_endpoints
        assert worker_metrics_endpoints(["a", "b"], 9100) == [
            ("a", 9100), ("b", 9101)]
        assert worker_metrics_endpoints(["a"], 0) == []


def test_metrics_endpoint_smoke_2rank(tmp_path):
    """Tier-1 endpoint smoke test: 2-rank world with the endpoints on, each
    rank validates its own registry, scrapes rank 0 over HTTP, and
    cross-checks the byte counters against the timeline per-op args."""
    base = _free_port_block(2)
    results = launch_world(
        2, os.path.join(DATA, "metrics_worker.py"),
        extra_env={
            "HVDTPU_METRICS_PORT": str(base),
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
        })
    assert_all_ok(results)


def test_metrics_4rank_compressed(tmp_path):
    """ISSUE 4 acceptance shape: 4-rank world under int8 wire compression —
    scraping any worker returns per-op histograms labeled
    algo/transport/compression plus raw/wire counters agreeing with the
    timeline."""
    base = _free_port_block(4)
    results = launch_world(
        4, os.path.join(DATA, "metrics_worker.py"),
        extra_env={
            "HVDTPU_METRICS_PORT": str(base),
            "HVDTPU_COMPRESSION": "int8",
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
        }, timeout=150)
    assert_all_ok(results)


def test_metrics_disabled_by_default():
    """HVDTPU_METRICS_PORT unset/0: no endpoint is bound, nothing breaks
    (the in-process dump still works — the worker asserts that itself)."""
    results = launch_world(
        2, os.path.join(DATA, "proc_worker.py"))
    assert_all_ok(results)


def test_stall_warning_and_gauge():
    """Process-mode stall-inspector regression (ISSUE 4 satellite): rank 1
    withholds one tensor; within stall_warn_secs rank 0 logs a warning
    naming the tensor and the missing rank, the ``stalled`` gauge flips to
    1, and everything completes cleanly once the laggard arrives."""
    results = launch_world(
        2, os.path.join(DATA, "stall_warn_worker.py"),
        extra_env={
            "HVDTPU_STALL_CHECK_TIME_SECONDS": "1",
            "TEST_STALL_HOLD_SECONDS": "8",
        }, timeout=120)
    assert_all_ok(results)
    rc0, out0, err0 = results[0]
    assert "STALL GAUGE FLIPPED" in out0
    # The warning names the tensor and the missing rank(s).
    assert "tensor 'withheld'" in err0, err0
    assert "waiting on ranks [1]" in err0, err0
    assert "ready on ranks [0]" in err0, err0


def test_golden_exposition_roundtrip():
    """ISSUE 13 satellite: scrape a LIVE worker's full /metrics, parse it
    with observability.py, re-render, re-parse, and diff — pins the parser
    against the entire current metric catalog (every family the native
    registry emits), not a hand-picked sample."""
    import numpy as np

    from horovod_tpu.observability import (MetricsServer,
                                           parse_prometheus_text,
                                           render_exposition, scrape)
    from tests.test_flightrec import _single_rank_core

    core = _single_rank_core()
    server = None
    try:
        # Touch every instrumented subsystem so the dump carries the full
        # catalog: ops (histogram labels), fusion, gauges, perf counters.
        for i in range(30):
            core.collective("allreduce", "rt", np.ones(2048, np.float32))
        core.collective("allgather", "rt2", np.ones(8, np.float32))
        server = MetricsServer(dump_fn=core.metrics_dump, port=0)
        server.start()
        text = scrape("127.0.0.1", server.port)
        assert "hvdtpu_op_seconds" in text  # a real, full dump
        assert "hvdtpu_clock_offset_us" in text
        parsed = parse_prometheus_text(text)
        reparsed = parse_prometheus_text(render_exposition(parsed))
        assert set(parsed) == set(reparsed)
        for fam, doc in parsed.items():
            assert doc["type"] == reparsed[fam]["type"], fam
            assert doc["help"] == reparsed[fam]["help"], fam
            assert doc["samples"] == reparsed[fam]["samples"], fam
    finally:
        if server is not None:
            server.stop()
        core.shutdown()


def test_clock_sync_gauges_exposed():
    """ISSUE 13 satellite: clock-sync quality rides /metrics as gauges.
    A single-rank world IS rank 0 (offset 0, err 0); a never-synced
    worker's err reads -1 — either way the series exist for the
    aggregator/console to flag degraded alignment."""
    from horovod_tpu.observability import parse_prometheus_text, sample_value
    from tests.test_flightrec import _single_rank_core

    core = _single_rank_core()
    try:
        parsed = parse_prometheus_text(core.metrics_dump())
        assert sample_value(parsed, "hvdtpu_clock_offset_us") == 0
        assert sample_value(parsed, "hvdtpu_clock_err_us") == 0
    finally:
        core.shutdown()


def test_hvdrun_metrics_flags_and_aggregator(tmp_path):
    """hvdrun --metrics-port end to end: scrape URLs printed, workers serve
    /metrics, the driver serves the merged world view on base+np while the
    job runs, and a summary line appears."""
    import subprocess
    import sys
    import threading
    import time as _time

    from conftest import subprocess_env
    from horovod_tpu.observability import parse_prometheus_text, scrape

    base = _free_port_block(3)
    agg_port = base + 2
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "for i in range(40):\n"
        "    hvd.allreduce(np.ones(1024, np.float32), name=f'x{i}')\n"
        "import time; time.sleep(3.0)\n"  # window for the driver scrape
        "hvd.shutdown()\n")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--metrics-port", str(base), "--metrics-interval", "0.5",
         sys.executable, str(script)],
        env=subprocess_env(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)

    world = {}

    def poll_driver():
        # The job secret is generated inside hvdrun, so the driver endpoint
        # rejects us (403) — proving the gate — until we read the merged
        # text through an authorized path: here we only assert the 403.
        import urllib.error
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline and proc.poll() is None:
            try:
                scrape("127.0.0.1", agg_port, timeout=2.0)
                world["open"] = True
                return
            except urllib.error.HTTPError as e:
                world["code"] = e.code
                return
            except Exception:
                _time.sleep(0.2)

    t = threading.Thread(target=poll_driver)
    t.start()
    out, err = proc.communicate(timeout=150)
    t.join(timeout=10)
    assert proc.returncode == 0, err
    # Scrape URLs printed at launch.
    assert f"http://localhost:{base}/metrics" in err, err
    assert f"/metrics (aggregated)" in err, err
    # The driver endpoint was up and secret-gated (hvdrun generated a job
    # secret, our bare scrape must have seen 403 — or the run finished
    # before our poll connected, in which case the thread saw nothing).
    assert world.get("code") == 403 or "open" not in world, world
    # Periodic one-line summary printed by the aggregator.
    assert "hvdrun metrics:" in err, err
