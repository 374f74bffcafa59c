"""Median `serve.batch` span: one batch through the engine as the runner
thread sees it (stack, host-to-device, execute, device-to-host)."""

from benchmark import common


def read(run):
    spans = run.spans.get("serve.batch")
    return 1e3 * common.median(spans) if spans else None
