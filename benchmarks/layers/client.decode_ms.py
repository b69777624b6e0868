"""`client.decode_ms` — client and wire: `from_wire` of a reply in
`GraphClient.execute` (series `client_decode_us`), per statement.  Lazy
columnar results decode their rows later, where the caller touches them."""
from benchmarks.lib.phases import series_ms


def read(ctx):
    return series_ms(ctx, "client_decode_us", scale=1e-3) if ctx["served"] else None
