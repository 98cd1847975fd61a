"""``device_idle_share`` where the end-to-end metric is a latency."""

from benchmark.layer_metrics import device_idle_share

META = {**device_idle_share.META, "moves": "bind_latency_p50_ms"}
read = device_idle_share.read
