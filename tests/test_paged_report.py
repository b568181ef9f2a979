"""The interleaved paged engine collects no per-step telemetry that only
the disaggregated engine reports (queue-depth samples, zero handoff
latencies), and its report still carries every number the launcher
prints for it."""
import contextlib
import io

from repro.launch.serve import main as serve_main
from test_paged import _paged_stub_engine
from test_roles import _disagg_stub_engine, _req


def _stream(n=6):
    return [_req(i, budget=3, arrival_s=0.5 * i) for i in range(n)]


def test_paged_report_leaves_disaggregated_fields_empty():
    rep = _paged_stub_engine(slots=4, cache_span=16, page_size=4,
                             num_pages=16).run(_stream())
    assert rep.completed == rep.handoffs == 6
    assert rep.handoff_latencies_s == []
    assert rep.queue_depth_peak == 0 and rep.queue_depth_mean == 0.0
    s = rep.summary()
    for key in ("completed", "total_new_tokens", "makespan_s", "goodput_rps",
                "goodput_tps", "ttft_p50_s", "ttft_p95_s", "tok_p50_s",
                "tok_p95_s", "decode_steps", "prefills", "occupancy",
                "slot_balance", "num_pages", "page_size",
                "page_occupancy_mean", "page_occupancy_peak",
                "fragmentation_mean", "peak_concurrency",
                "decode_stall_p50_s", "decode_stall_p95_s"):
        assert key in s, key
    assert "queue_depth_peak" not in s and "handoff_p50_s" not in s


def test_disaggregated_report_keeps_handoffs_and_queue_depth():
    rep = _disagg_stub_engine(slots=4, cache_span=16, page_size=4,
                              num_pages=16, prefill_workers=2,
                              decode_workers=2).run(_stream())
    assert len(rep.handoff_latencies_s) == rep.handoffs == 6
    assert rep.queue_depth_peak >= 1
    s = rep.summary()
    assert s["queue_depth_peak"] == rep.queue_depth_peak
    assert s["handoff_p95_s"] >= s["handoff_p50_s"] >= 0.0


def test_launcher_prints_a_paged_run():
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        serve_main(["--arch", "granite-3-8b", "--scheduler", "paged",
                    "--num-requests", "4", "--batch", "2", "--prompt-len",
                    "8", "--max-new-tokens", "4", "--page-size", "4",
                    "--prefill-chunk", "4"])
    text = out.getvalue()
    assert "[paged] 4/4 requests" in text
    assert "pages=" in text and "peak_concurrency=" in text
    assert "roles:" not in text
