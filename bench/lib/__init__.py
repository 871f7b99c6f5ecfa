"""The benchmark's own machinery: loading parts by name, the run of one
cell, and the reductions from spans, counters and traces to metrics."""
