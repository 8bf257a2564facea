"""The benchmark's yardstick: graphs, reference, work counts, peaks, trace."""
