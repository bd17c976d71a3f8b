"""Share of packed slots that hold a real token, over the window: program
counters ``sequence.tokens_packed`` and ``sequence.pad_tokens``."""

from benchmarks.metrics.common import delta


def read(rec):
    tokens, pad = delta(rec, "sequence.tokens_packed"), delta(rec, "sequence.pad_tokens")
    if tokens is None or pad is None or tokens + pad <= 0:
        return None
    return 100.0 * tokens / (tokens + pad)
