"""Median device-busy time inside one run of a bucket executable;
`batch_service_ms` minus this is the host's part. Device trace. The same
reading as `step_device_ms`, over the serving runner's program."""

from benchmark.layer_metrics.step_device_ms import read  # noqa: F401
