"""Mean host milliseconds per cell in ``MigrationAnalyzer.decide``."""


def read(run):
    return 1e3 * run.spans.get("decide", 0.0) / run.cells if run.cells \
        else None
