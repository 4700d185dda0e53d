"""``closed``: keep the admission queue at ``fill_rows`` pending rows,
topping it up in ``chunk_rows``-row chunks, so that nothing is shed
and the batcher always has a full top-rung bucket to form."""


def run(gen) -> None:
    chunk = int(gen.mix["chunk_rows"])
    fill = int(gen.mix["fill_rows"])
    queue = gen.s.runtime.queue
    while not gen.stopping():
        room = fill - queue.pending
        if room < chunk:
            gen.idle(0.0005)
            continue
        for _ in range(room // chunk):
            gen.offer(chunk)
