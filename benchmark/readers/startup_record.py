"""A number of the program's own start-up record, read in the run's own
process and cut where the window opened: ``setup_s`` after the process
started, as the OS records the start (``startup.process_start``).

The metric's file names the number (``field``): a key of
``startup.startup_report`` (``programs``, ``trace_s``, ``lower_s``, a
span such as ``startup/runtime``), or several joined by ``+``, summed.
``None`` where the program keeps no record (a program from before it),
where the record overflowed before the cut, or where it lacks the key."""


def read(facts: dict, spec: dict):
    try:
        from deepvision_tpu.startup import process_start, startup_report
    except ImportError:
        return None
    cut = process_start() + facts["end_to_end"]["setup_s"]
    report = startup_report(until=cut)
    if not report or not report["complete"]:
        return None
    values = [report.get(k) for k in spec["field"].split("+")]
    return None if None in values else sum(values)
