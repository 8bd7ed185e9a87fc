"""One module per traffic ``kind``: ``run(cell) -> dict``."""
