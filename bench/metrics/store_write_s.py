"""Host time of programming the store in set-up: create, calibrate and
every MemoryStore.write, to block_until_ready, in s."""


def read(r):
    return r.ctx.timers.get("store_write_s")
