"""Bytes the sensor-decode kernel must move for one call."""


def decode_bytes(rows: int, nb: int) -> int:
    """Payload bytes read (uint8), float32 features written, and the
    per-record scale, zero point and length read (4 bytes each)."""
    return rows * nb * (1 + 4) + rows * 12
