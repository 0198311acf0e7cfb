"""Fault-tolerant block-stream transport: serve hosts -> learner.

- `framing` — length-prefixed CRC frames, versioned handshake, codecs
- `BlockStreamPublisher` — serve side: spools finished Blocks, streams
  them at-least-once with resume-on-reconnect, applies checkpoints
- `IngestService` — learner side: N host connections, seq dedup, skew
  stamping, replay fan-in, checkpoint broadcast
- `podloop` — the two process bodies (`--role serve|learner`) used by
  the module's own CLI and the transport tests
"""

from r2d2_tpu.transport import framing
from r2d2_tpu.transport.ingest import IngestService
from r2d2_tpu.transport.publisher import BlockStreamPublisher

__all__ = ["framing", "BlockStreamPublisher", "IngestService"]
