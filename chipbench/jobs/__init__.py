"""Jobs the benchmark can drive, one per traffic ``kind``."""
