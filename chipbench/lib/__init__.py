"""The benchmark's yardstick: traffic, trace reduction, FLOP and byte
counts, and the harness plumbing shared by every kind of cell."""
