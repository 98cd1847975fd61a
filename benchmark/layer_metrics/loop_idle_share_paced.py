"""``loop_idle_share`` where the end-to-end metric is a latency."""

from benchmark.layer_metrics import loop_idle_share

META = {**loop_idle_share.META, "moves": "bind_latency_p50_ms"}
read = loop_idle_share.read
