"""Share of a pipelined streamed assessment that the host spent blocked on
the device: the chunks' ``ChunkStats.chunk_eval_seconds`` over the runs'
``wall_seconds``, summed over the window's assessments."""


def read(run):
    stats = [s["stats"] for s in run.steps if s.get("stats") is not None]
    wall = sum(st.wall_seconds for st in stats)
    if wall <= 0:
        return None
    return sum(sum(st.chunk_eval_seconds) for st in stats) / wall
