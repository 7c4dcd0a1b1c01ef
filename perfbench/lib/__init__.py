"""The benchmark harness's own code: traffic, reference, reduction, device record."""
