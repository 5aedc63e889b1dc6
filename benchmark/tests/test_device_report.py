"""``memory_peak_bytes`` is the fullest chip's live peak plus what the
runtime reserved for its programs' temporaries."""
import run as harness


class Chip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_live_and_reserved_of_the_fullest_chip():
    chips = [Chip({"peak_bytes_in_use": 700, "peak_bytes_reserved": 100}),
             Chip({"peak_bytes_in_use": 600, "peak_bytes_reserved": 1800}),
             Chip({"peak_bytes_in_use": 9000, "peak_bytes_reserved": 9000})]
    report = harness.device_report(chips, 2)      # the cell uses two
    assert report["memory_peak_bytes"] == 2400
    assert (report["live_peak_bytes"], report["reserved_peak_bytes"]) == \
        (600, 1800)
    assert report["count"] == 3


def test_a_backend_without_counters_reports_none():
    report = harness.device_report([Chip(None)], 1)
    assert report["memory_peak_bytes"] is None
