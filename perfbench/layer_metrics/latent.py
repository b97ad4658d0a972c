"""KV manager, a latent pool: ``latent.bytes_per_token`` is what one cached
token costs across the layers as the pool stores it (``stats()``
``latent_bytes_per_token``, a fixed number: DeepSeek-V3's 576 values a layer
are 5,760 B over 5 layers in bfloat16, and 6,400 B in rows stored 640 wide).
A program whose pool keeps K and V per kv head reports 0 there, or nothing,
and reads ``None``."""


def read(name: str, lc: dict):
    if name != "latent.bytes_per_token":
        return None
    value = (lc.get("stats1") or {}).get("latent_bytes_per_token")
    return float(value) if value else None
