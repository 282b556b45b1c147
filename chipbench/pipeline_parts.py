"""What the readers of a pipeshard step's account share: the table the
program's capture makes of its traced steps on the device's clock
(``alpa_tpu.telemetry.trace.last_capture().pipeline_time()``: by mesh, the
seconds a mesh's stage programs ran and the seconds it idled by cause,
``boundary``, ``upstream``, ``dispatch``, ``edge``, which add up to the
steps' envelope, and the seconds its chips' op line spent in a collective;
``alpa_tpu/telemetry/perf.py`` ``_device_bubbles`` says what each cause is).
The arithmetic is the program's; a reader picks its fields.  The twin of
``device_parts.py``.
"""

CAUSES = ("boundary", "upstream", "dispatch", "edge")


def table():
    """The newest capture's account by mesh, or None: no capture was made,
    no pipeshard step was traced (every one-chip cell), none joined the
    device events (the CPU has none), or the program is one from before
    the captures kept an account."""
    try:
        from alpa_tpu.telemetry import trace
        capture = trace.last_capture()
        found = capture.pipeline_time() if capture is not None else None
    except (ImportError, AttributeError):
        return None
    return found or None


def share_pct(field: str):
    """``field`` as a share of the traced steps' envelope, in per cent:
    the mean over meshes."""
    found = table()
    if not found:
        return None
    return 100.0 * sum(row[field] / row["envelope_s"]
                       for row in found.values()) / len(found)
