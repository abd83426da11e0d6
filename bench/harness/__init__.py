"""The benchmark's yardstick: spec loading, the run loop, trace reduction,
peaks, FLOP counts and the compile counter."""
