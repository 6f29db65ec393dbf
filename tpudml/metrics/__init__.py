from tpudml.metrics.profiler import annotate, trace
from tpudml.metrics.writer import MetricsWriter, get_summary_writer

__all__ = ["MetricsWriter", "annotate", "get_summary_writer", "trace"]
