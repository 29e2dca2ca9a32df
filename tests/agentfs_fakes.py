"""What the tests' duck-typed agent file systems share: ``open_read``
and ``read_many`` made of the fake's own ``open`` / ``read_at`` /
``close``, so that each fake keeps counting what it counts and failing
where it fails."""

from pbs_plus_tpu.agent.agentfs import FirstReadError


class OpenReadViaCalls:
    """Mix-in.  ``honours_read = False`` answers as an agent that
    predates the ``read`` key does: a bare handle, nothing read.
    ``knows_read_many`` is False unless a fake sets it: the answer of an
    agent without the method, so the fakes that count opens and reads go
    on counting a file at a time."""

    honours_read = True
    knows_read_many = False

    async def open_read(self, rel: str, n: int) -> tuple[int, bytes, bool]:
        handle = await self.open(rel)
        if not self.honours_read:
            return handle, b"", False
        try:
            data = await self.read_at(handle, 0, n)
        except ConnectionError:
            raise
        except Exception as e:
            await self.close(handle)
            raise FirstReadError(str(e)) from e
        if len(data) < n:
            await self.close(handle)
            return 0, data, True
        return handle, data, False

    async def read_many(self, paths: list, budget: int):
        """``AgentFSClient.read_many``'s answer: bytes, the exception
        ``open_read`` would have raised, or None from the first file
        that passes what is left of the budget."""
        if not self.knows_read_many:
            return None
        out: list = []
        left = budget
        for rel in paths:
            try:
                handle, data, eof = await self.open_read(rel, left + 1)
            except ConnectionError:
                raise
            except Exception as e:
                out.append(e)
                continue
            if not eof:
                await self.close(handle)
                break
            left -= len(data)
            out.append(data)
        return out + [None] * (len(paths) - len(out))
