"""``resident_topn_loop``: ``resident_loop`` for suites whose Histograms
run over more values than deequ details (``max_detail_bins``): the same
caller, table and entry point; a Histogram's answer goes to the comparison
as ``chipbench.topbins.TopBins`` (the metric's ``number_of_bins`` and the
bins it reported), whose ``!=`` against the reference's full histogram is
the rule for a truncated answer."""

from __future__ import annotations

from chipbench import suite_build
from chipbench.drivers import resident_loop
from chipbench.drivers.resident_loop import rows_per_operation, slices  # noqa: F401
from chipbench.topbins import TopBins


class Driver(resident_loop.Driver):
    def _run(self, _k: int = 0):
        from deequ_tpu import VerificationSuite

        result = (
            VerificationSuite.on_data(self.table)
            .add_check(self.check)
            .add_required_analyzers(self.analyzers)
            .run()
        )
        answers = suite_build.answers_of(result, self.analyzers)
        values = answers["values"]
        for i, (entry, analyzer) in enumerate(
                zip(self.suite["analyzers"], self.analyzers)):
            if entry["analyzer"] == "Histogram" and values[i] is not None:
                distribution = result.metrics[analyzer].value.get()
                values[i] = TopBins(distribution.number_of_bins, values[i])
        return self.rows, answers
