"""Shared code of the benchmark: the manifest, the device, seeded data, the
timed window, the trace reduction and the runner that ties them together."""
