"""``mfu`` of the fleet cells: the same reading, split by name because
it moves ``report_latency_p95_ms`` there."""

from benchlib import load_named

read = load_named("metrics", "mfu").read
