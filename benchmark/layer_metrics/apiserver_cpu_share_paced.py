"""``apiserver_cpu_share`` where the end-to-end metric is a latency."""

from benchmark.layer_metrics import apiserver_cpu_share

META = {**apiserver_cpu_share.META, "moves": "bind_latency_p50_ms"}
read = apiserver_cpu_share.read
