"""The benchmark's harness: cell loading, traffic generation, the round
loop, the validator, the probe and the trace reduction."""
