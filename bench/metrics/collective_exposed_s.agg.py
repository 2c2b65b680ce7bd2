"""collective_exposed_s.agg: per aggregation, the device time in which a
collective runs and no other leaf operation does, averaged over the chips
(``agg_mesh_cell.exposed_collective_s``), over the traced window."""


def read(cell, out):
    secs = out.facts.get("exposed_collective_s")
    return None if secs is None else secs / out.facts["rounds"]
