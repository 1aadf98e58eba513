"""The benchmark's yardstick as far as it knows no model: the manifest, the
runner and its windows, the general traffic draws, the comparison's measures
and verdict, peaks and the trace reduction. What knows a model is a family
(``benchmarks/families/``), and only code there imports the system under
test."""
