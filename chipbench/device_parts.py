"""What the readers of device time by part of the model share: the table
the program's capture reduced from the traced part
(``alpa_tpu.telemetry.trace.last_capture().device_time()``: device seconds
by chip, by program and by part of the model, the part read off the
compiled programs' ``op_name``; ``alpa_tpu/telemetry/device_time.py``).
The arithmetic is the program's; a reader picks its program and its parts.
"""


def table():
    """The newest capture's table, or None: no capture was made, or the
    program is one from before the captures reduced their device time."""
    try:
        from alpa_tpu.telemetry import trace
        capture = trace.last_capture()
    except (ImportError, AttributeError):
        return None
    return capture.device_time() if capture is not None else None


def scoped(entries):
    """``entries`` (a program's, by chip) if they are to be trusted: some
    run, and at most a tenth of their device seconds under no ``op_name``
    (a program read back from a compile cache that an earlier tree
    filled keeps that tree's names); else None."""
    total = sum(sum(e["parts"].values()) for e in entries)
    if not total or sum(e["unscoped_s"] for e in entries) > 0.1 * total:
        return None
    return entries


def program(name: str):
    """The entry of the program ``name`` on the first chip that ran it in
    the traced part, or None (see ``scoped``)."""
    found = table()
    for programs in (found or {"programs": {}})["programs"].values():
        if name in programs:
            return programs[name] if scoped([programs[name]]) else None
    return None


def part_ms_a_run(entry, *parts) -> float:
    """Milliseconds of ``parts`` (each with the parts inside it) in one
    run of the program, on average over its runs."""
    from alpa_tpu.telemetry.device_time import part_seconds
    return 1e3 * sum(part_seconds(entry, p) for p in parts) / entry["runs"]
