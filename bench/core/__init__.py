"""The benchmark's yardstick: cell lookup, weights and batches from the seed,
FLOP and byte counts, chip peaks, trace reduction and statistics."""
