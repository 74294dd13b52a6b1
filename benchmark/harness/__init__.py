"""The benchmark's harness: everything that belongs to no single
configuration, traffic mix or per-layer metric."""
