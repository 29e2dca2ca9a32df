"""What the tests' duck-typed agent file systems share: ``open_read``
made of the fake's own ``open`` / ``read_at`` / ``close``, so that each
fake keeps counting what it counts and failing where it fails."""

from pbs_plus_tpu.agent.agentfs import FirstReadError


class OpenReadViaCalls:
    """Mix-in.  ``honours_read = False`` answers as an agent that
    predates the ``read`` key does: a bare handle, nothing read."""

    honours_read = True

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
