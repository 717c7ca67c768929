"""Rows of K/V the active slots hold over rows the decode program gathers,
%, summed over the window's decode ticks: the ``live_rows`` and
``gathered_rows`` attrs of the ``serve.decode`` spans (the program gathers
every slot's whole page-table row whatever is live).  None where the spans
carry no such attrs."""


def read(record):
    ticks = [s.attrs for s in record.get("spans", [])
             if s.name == "serve.decode" and s.attrs
             and "gathered_rows" in s.attrs]
    gathered = sum(a["gathered_rows"] for a in ticks)
    if not gathered:
        return None
    return 100.0 * sum(a["live_rows"] for a in ticks) / gathered
