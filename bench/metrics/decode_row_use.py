"""Share of decoded arena rows that were bound to a request, summed over
every decode step of the window (the rest are warm, idle or free rows the
step computes anyway), in percent."""


def read(view):
    rows = sum(d.rows for d in view.decodes)
    return 100.0 * sum(d.live for d in view.decodes) / rows if rows else None
