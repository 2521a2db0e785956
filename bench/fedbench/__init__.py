"""The benchmark's own code: registry of cells, data, plain references,
analytic work counts, probes, trace reduction and the correctness check.
Nothing here is imported by the program."""
