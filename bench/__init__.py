"""The benchmark: harness, generator, reference and trace reduction."""
