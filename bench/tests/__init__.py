"""Tests of the benchmark harness: discovery by file name, the trace
reduction, the metric arithmetic, the references and every driver end to
end at a tiny size on the CPU."""
