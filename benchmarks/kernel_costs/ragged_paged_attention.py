"""The ragged kernel's decode rows cost what decode-path paged attention
costs; its chunk rows are not costed yet (PERF.md, Open questions)."""

from .paged_attention import cost  # noqa: F401
