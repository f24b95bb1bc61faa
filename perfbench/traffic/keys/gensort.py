"""gensort: Sort Benchmark records (100 bytes: a 10-byte key, a 90-byte
payload) as (25, n) uint32 word planes, each word four bytes big-endian.
Keys are uniform random bytes (gensort's Indy category); the payload
follows gensort's layout as recalled, with record number i:

    bytes 10-11   00 11
    bytes 12-43   i as 32 uppercase ASCII hex digits
    bytes 44-47   88 99 AA BB
    bytes 48-95   12 groups of 4 equal hex digits, nibble j % 8 of i
    bytes 96-99   CC DD EE FF

(`perfbench/configs/sortbench_records_p4.json` lists this under
`assumed`.)"""

ZEROS = 0x30303030        # "0000"


def _hex(v):
    """Nibbles 0..15 -> their uppercase ASCII hex digit."""
    import jax.numpy as jnp
    return jnp.where(v < 10, v + 0x30, v + 0x37)


def draw(key, n: int, mix: dict):
    """n records from a jax PRNG key, traced inside one jitted call."""
    import jax
    import jax.numpy as jnp
    u32 = jnp.uint32
    bits = jax.random.bits(key, (3, n), u32)
    i = jnp.arange(n, dtype=u32)
    nib = [(i >> u32(4 * (7 - k))) & u32(15) for k in range(8)]   # msb first
    dig = [_hex(v) for v in nib]
    full = lambda c: jnp.full((n,), c, u32)
    words = [bits[0], bits[1], (bits[2] & u32(0xFFFF0000)) | u32(0x0011)]
    words += [full(ZEROS)] * 6
    words += [(dig[0] << 24) | (dig[1] << 16) | (dig[2] << 8) | dig[3],
              (dig[4] << 24) | (dig[5] << 16) | (dig[6] << 8) | dig[7]]
    words.append(full(0x8899AABB))
    words += [dig[7 - j % 8] * u32(0x01010101) for j in range(12)]
    words.append(full(0xCCDDEEFF))
    return jnp.stack(words)
