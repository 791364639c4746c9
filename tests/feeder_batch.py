"""One batch through a DeviceFeeder, handed on as its dispatcher hands
a batch on, for the tests that drive the batch path directly."""

from __future__ import annotations

import asyncio

from garage_tpu.block.feeder import _Item


async def run_batch(f, op: str, datas: list) -> list:
    """`datas` as one batch of `op`; -> each item's result in order, an
    item's error as its result."""
    loop = asyncio.get_running_loop()
    items = [_Item(op, d, loop.create_future()) for d in datas]
    f._dispatch(items)
    return list(await asyncio.gather(*[it.future for it in items],
                                     return_exceptions=True))
