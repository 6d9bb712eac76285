"""The benchmark's own yardstick: data, traffic, reference, check, peaks,
work and trace reduction."""
