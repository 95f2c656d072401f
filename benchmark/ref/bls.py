"""Plain BLS12-381 for the benchmark: keys, signing and FastAggregateVerify.

Pure Python integers, written for the benchmark from the published
definitions and imported by nothing in the program:

- the curve and its constants (draft-irtf-cfrg-pairing-friendly-curves,
  BLS12-381): Fp, Fp2 = Fp[u]/(u^2+1), Fp6 = Fp2[v]/(v^3-(1+u)),
  Fp12 = Fp6[w]/(w^2-v); E: y^2 = x^3+4 and its M-twist E': y^2 = x^3+4(1+u);
- hash_to_curve for BLS12381G2_XMD:SHA-256_SSWU_RO_ (RFC 9380 sections
  5.3.1, 5.2, 6.6.2, appendix E.3 for the 3-isogeny, 8.8.2 for h_eff);
- the ZCash point encoding the Ethereum BLS signature scheme uses;
- the optimal ate pairing: an affine Miller loop over the twist with the
  lines evaluated at P, and the final exponentiation (p^12-1)/r split into
  its easy part and a hard part computed as the cube, via
  3(p^4-p^2+1)/r = (x-1)^2 (x+p)(x^2+p^2-1) + 3 (checked at import).

Speed is secondary: a FastAggregateVerify takes tens of milliseconds here.
"""
from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X = -0xD201000000010000  # the curve parameter (negative)
G1 = (0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
      0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1)
G2 = ((0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
       0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
      (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
       0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE))
H_EFF_G2 = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# --- Fp2 ---------------------------------------------------------------------
# Elements are (a, b) = a + b*u.

F2_ZERO, F2_ONE = (0, 0), (1, 0)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    return ((a[0] * b[0] - a[1] * b[1]) % P, (a[0] * b[1] + a[1] * b[0]) % P)


def f2_muls(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def f2_sqr(a):
    return f2_mul(a, a)


def f2_inv(a):
    d = pow((a[0] * a[0] + a[1] * a[1]) % P, P - 2, P)
    return (a[0] * d % P, -a[1] * d % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_pow(a, e: int):
    out = F2_ONE
    while e:
        if e & 1:
            out = f2_mul(out, a)
        a = f2_sqr(a)
        e >>= 1
    return out


def fp_sqrt(a: int):
    """A square root in Fp (p = 3 mod 4), or None."""
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def f2_is_square(a) -> bool:
    n = (a[0] * a[0] + a[1] * a[1]) % P
    return n == 0 or pow(n, (P - 1) // 2, P) == 1


def f2_sqrt(a):
    """A square root in Fp2, or None: through the norm (complex method)."""
    a0, a1 = a
    if a1 == 0:
        s = fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        s = fp_sqrt(-a0 % P)
        return None if s is None else (0, s)
    alpha = fp_sqrt((a0 * a0 + a1 * a1) % P)
    if alpha is None:
        return None
    inv2 = (P + 1) // 2
    delta = (a0 + alpha) * inv2 % P
    x0 = fp_sqrt(delta)
    if x0 is None:
        x0 = fp_sqrt((a0 - alpha) * inv2 % P)
        if x0 is None:
            return None
    x1 = a1 * pow(2 * x0, P - 2, P) % P
    root = (x0, x1)
    return root if f2_sqr(root) == (a0 % P, a1 % P) else None


# --- Fp6 and Fp12 ------------------------------------------------------------
XI = (1, 1)  # v^3 = xi = 1 + u, w^6 = xi


def f6_add(a, b):
    return (f2_add(a[0], b[0]), f2_add(a[1], b[1]), f2_add(a[2], b[2]))


def f6_sub(a, b):
    return (f2_sub(a[0], b[0]), f2_sub(a[1], b[1]), f2_sub(a[2], b[2]))


def f6_neg(a):
    return (f2_neg(a[0]), f2_neg(a[1]), f2_neg(a[2]))


def f6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0, t1, t2 = f2_mul(a0, b0), f2_mul(a1, b1), f2_mul(a2, b2)
    c0 = f2_add(t0, f2_mul(XI, f2_add(f2_mul(a1, b2), f2_mul(a2, b1))))
    c1 = f2_add(f2_add(f2_mul(a0, b1), f2_mul(a1, b0)), f2_mul(XI, t2))
    c2 = f2_add(f2_add(f2_mul(a0, b2), t1), f2_mul(a2, b0))
    return (c0, c1, c2)


def f6_mul_v(a):
    return (f2_mul(XI, a[2]), a[0], a[1])


def f6_inv(a):
    a0, a1, a2 = a
    t0 = f2_sub(f2_sqr(a0), f2_mul(XI, f2_mul(a1, a2)))
    t1 = f2_sub(f2_mul(XI, f2_sqr(a2)), f2_mul(a0, a1))
    t2 = f2_sub(f2_sqr(a1), f2_mul(a0, a2))
    den = f2_add(f2_mul(a0, t0), f2_mul(XI, f2_add(f2_mul(a2, t1), f2_mul(a1, t2))))
    d = f2_inv(den)
    return (f2_mul(t0, d), f2_mul(t1, d), f2_mul(t2, d))


F6_ZERO = (F2_ZERO, F2_ZERO, F2_ZERO)
F6_ONE = (F2_ONE, F2_ZERO, F2_ZERO)
F12_ONE = (F6_ONE, F6_ZERO)


def f12_mul(a, b):
    t0, t1 = f6_mul(a[0], b[0]), f6_mul(a[1], b[1])
    c1 = f6_sub(f6_sub(f6_mul(f6_add(a[0], a[1]), f6_add(b[0], b[1])), t0), t1)
    return (f6_add(t0, f6_mul_v(t1)), c1)


def f12_sqr(a):
    return f12_mul(a, a)


def f12_conj(a):
    return (a[0], f6_neg(a[1]))


def f12_inv(a):
    t = f6_inv(f6_sub(f6_mul(a[0], a[0]), f6_mul_v(f6_mul(a[1], a[1]))))
    return (f6_mul(a[0], t), f6_neg(f6_mul(a[1], t)))


# Frobenius: an element is sum_j c_j w^j with c_j in Fp2 (j = 0..5), and
# (c w^j)^p = conj(c) * gamma^j * w^j with gamma = xi^((p-1)/6).
_GAMMA = f2_pow(XI, (P - 1) // 6)
_GAMMA_POW = [F2_ONE]
for _ in range(5):
    _GAMMA_POW.append(f2_mul(_GAMMA_POW[-1], _GAMMA))


def _to_w(a):
    (c0, c1, c2), (d0, d1, d2) = a  # a0 + a1 w with a_i in Fp6 over v = w^2
    return [c0, d0, c1, d1, c2, d2]


def _from_w(c):
    return ((c[0], c[2], c[4]), (c[1], c[3], c[5]))


def f12_frob(a):
    return _from_w([f2_mul(f2_conj(c), g) for c, g in zip(_to_w(a), _GAMMA_POW)])


def f12_pow_u(a, e: int):
    """a^e for e >= 0, square and multiply from the top bit."""
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_sqr(out)
        if bit == "1":
            out = f12_mul(out, a)
    return out


# --- curve points ------------------------------------------------------------
# Jacobian (X, Y, Z) over a field given by its ops; None is the point at
# infinity.


class Field:
    def __init__(self, add, sub, mul, inv, zero, one, b):
        self.add, self.sub, self.mul, self.inv = add, sub, mul, inv
        self.zero, self.one, self.b = zero, one, b


FP = Field(lambda a, b: (a + b) % P, lambda a, b: (a - b) % P,
           lambda a, b: a * b % P, lambda a: pow(a, P - 2, P), 0, 1, 4)
FP2 = Field(f2_add, f2_sub, f2_mul, f2_inv, F2_ZERO, F2_ONE, (4, 4))


def pt_double(F, p):
    if p is None:
        return None
    x, y, z = p
    if y == F.zero:
        return None
    a = F.mul(x, x)
    b = F.mul(y, y)
    c = F.mul(b, b)
    t = F.add(x, b)
    d = F.sub(F.sub(F.mul(t, t), a), c)
    d = F.add(d, d)
    e = F.add(F.add(a, a), a)
    f = F.mul(e, e)
    x3 = F.sub(f, F.add(d, d))
    c8 = F.add(c, c)
    c8 = F.add(c8, c8)
    c8 = F.add(c8, c8)
    y3 = F.sub(F.mul(e, F.sub(d, x3)), c8)
    yz = F.mul(y, z)
    return (x3, y3, F.add(yz, yz))


def pt_add(F, p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1, z2z2 = F.mul(z1, z1), F.mul(z2, z2)
    u1, u2 = F.mul(x1, z2z2), F.mul(x2, z1z1)
    s1 = F.mul(y1, F.mul(z2, z2z2))
    s2 = F.mul(y2, F.mul(z1, z1z1))
    if u1 == u2:
        return pt_double(F, p) if s1 == s2 else None
    h = F.sub(u2, u1)
    rr = F.sub(s2, s1)
    hh = F.mul(h, h)
    hhh = F.mul(h, hh)
    v = F.mul(u1, hh)
    x3 = F.sub(F.sub(F.mul(rr, rr), hhh), F.add(v, v))
    y3 = F.sub(F.mul(rr, F.sub(v, x3)), F.mul(s1, hhh))
    return (x3, y3, F.mul(F.mul(z1, z2), h))


def pt_mul(F, p, k: int):
    out = None
    for bit in bin(k)[2:] if k > 0 else "":
        out = pt_double(F, out)
        if bit == "1":
            out = pt_add(F, out, p)
    return out


def pt_neg(F, p):
    return None if p is None else (p[0], F.sub(F.zero, p[1]), p[2])


def to_affine(F, p):
    if p is None:
        return None
    zi = F.inv(p[2])
    zi2 = F.mul(zi, zi)
    return (F.mul(p[0], zi2), F.mul(p[1], F.mul(zi, zi2)))


def from_affine(F, a):
    return None if a is None else (a[0], a[1], F.one)


def on_curve(F, a) -> bool:
    x, y = a
    return F.mul(y, y) == F.add(F.mul(F.mul(x, x), x), F.b)


def in_subgroup(F, a) -> bool:
    return pt_mul(F, from_affine(F, a), R) is None


# --- encodings -----------------------------------------------------------------

HALF_P = (P - 1) // 2


def g1_compress(a) -> bytes:
    if a is None:
        return bytes([0xC0]) + bytes(47)
    x, y = a
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > HALF_P else 0)
    return bytes(out)


def g1_decompress(data: bytes):
    """Affine point or None for infinity; raises ValueError if invalid."""
    if len(data) != 48 or not data[0] & 0x80:
        raise ValueError("G1: not a compressed 48-byte point")
    if data[0] & 0x40:
        if data[0] != 0xC0 or any(data[1:]):
            raise ValueError("G1: bad infinity encoding")
        return None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    if x >= P:
        raise ValueError("G1: x out of range")
    y = fp_sqrt((x * x * x + 4) % P)
    if y is None:
        raise ValueError("G1: not on the curve")
    if (y > HALF_P) != bool(data[0] & 0x20):
        y = P - y
    return (x, y)


def _f2_sign(y) -> bool:
    return y[1] > HALF_P if y[1] else y[0] > HALF_P


def g2_compress(a) -> bytes:
    if a is None:
        return bytes([0xC0]) + bytes(95)
    x, y = a
    out = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if _f2_sign(y) else 0)
    return bytes(out)


def g2_decompress(data: bytes):
    if len(data) != 96 or not data[0] & 0x80:
        raise ValueError("G2: not a compressed 96-byte point")
    if data[0] & 0x40:
        if data[0] != 0xC0 or any(data[1:]):
            raise ValueError("G2: bad infinity encoding")
        return None
    x1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= P or x1 >= P:
        raise ValueError("G2: x out of range")
    x = (x0, x1)
    y = f2_sqrt(f2_add(f2_mul(f2_sqr(x), x), (4, 4)))
    if y is None:
        raise ValueError("G2: not on the curve")
    if _f2_sign(y) != bool(data[0] & 0x20):
        y = f2_neg(y)
    return (x, y)


# --- hash_to_curve (RFC 9380) ---------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, length: int) -> bytes:
    ell = (length + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + length.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    out = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, out[-1]))
        out.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(out)[:length]


def hash_to_field_fp2(msg: bytes, count: int, dst: bytes):
    uniform = expand_message_xmd(msg, dst, count * 2 * 64)
    return [tuple(int.from_bytes(uniform[64 * (j + 2 * i):64 * (j + 2 * i + 1)], "big") % P
                  for j in range(2)) for i in range(count)]


SSWU_A = (0, 240)
SSWU_B = (1012, 1012)
SSWU_Z = (P - 2, P - 1)  # -(2 + u)


def _sgn0(a) -> int:
    return (a[0] & 1) | (a[0] == 0 and a[1] & 1)


def map_to_curve_sswu(u):
    """Simplified SWU onto E': y^2 = x^3 + 240u x + 1012(1+u)."""
    zu2 = f2_mul(SSWU_Z, f2_sqr(u))
    den = f2_add(f2_sqr(zu2), zu2)
    if den == F2_ZERO:
        x1 = f2_mul(SSWU_B, f2_inv(f2_mul(SSWU_Z, SSWU_A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(SSWU_B), f2_inv(SSWU_A)), f2_add(F2_ONE, f2_inv(den)))

    def g(x):
        return f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(SSWU_A, x)), SSWU_B)

    gx1 = g(x1)
    if f2_is_square(gx1):
        x, y = x1, f2_sqrt(gx1)
    else:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(g(x))
    if _sgn0(u) != _sgn0(y):
        y = f2_neg(y)
    return (x, y)


def _k(h0: int, h1: int):
    return (h0, h1)


ISO_XNUM = [
    _k(0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
       0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    _k(0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    _k(0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
       0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    _k(0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
]
ISO_XDEN = [
    _k(0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    _k(0xC, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    _k(1, 0),
]
ISO_YNUM = [
    _k(0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
       0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    _k(0, 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    _k(0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
       0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    _k(0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
]
ISO_YDEN = [
    _k(0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
       0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    _k(0, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    _k(0x12, 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    _k(1, 0),
]


def _poly(coeffs, x):
    out = F2_ZERO
    for c in reversed(coeffs):
        out = f2_add(f2_mul(out, x), c)
    return out


def iso_map(pt):
    """The 3-isogeny E' -> E (RFC 9380 appendix E.3)."""
    x, y = pt
    xn, xd = _poly(ISO_XNUM, x), _poly(ISO_XDEN, x)
    yn, yd = _poly(ISO_YNUM, x), _poly(ISO_YDEN, x)
    return (f2_mul(xn, f2_inv(xd)), f2_mul(y, f2_mul(yn, f2_inv(yd))))


def hash_to_g2(msg: bytes, dst: bytes = DST):
    """Affine point of G2."""
    u0, u1 = hash_to_field_fp2(msg, 2, dst)
    q0 = from_affine(FP2, iso_map(map_to_curve_sswu(u0)))
    q1 = from_affine(FP2, iso_map(map_to_curve_sswu(u1)))
    return to_affine(FP2, pt_mul(FP2, pt_add(FP2, q0, q1), H_EFF_G2))


# --- keys and signatures -------------------------------------------------------


def sk_to_pk(sk: int) -> bytes:
    return g1_compress(to_affine(FP, pt_mul(FP, from_affine(FP, G1), sk % R)))


def sign(sk: int, msg: bytes) -> bytes:
    h = from_affine(FP2, hash_to_g2(msg))
    return g2_compress(to_affine(FP2, pt_mul(FP2, h, sk % R)))


def key_validate(pk: bytes):
    """The affine point of a valid public key, else None (KeyValidate)."""
    try:
        a = g1_decompress(pk)
    except ValueError:
        return None
    if a is None or not in_subgroup(FP, a):
        return None
    return a


def aggregate_pubkeys(points) -> bytes:
    """eth_aggregate_pubkeys over already validated affine points."""
    acc = None
    for a in points:
        acc = pt_add(FP, acc, from_affine(FP, a))
    return g1_compress(to_affine(FP, acc))


# --- pairing --------------------------------------------------------------------


def _line(t, q, p):
    """(T', line at P): the tangent (q is None) or chord through the twist
    points t and q, evaluated at P and scaled by w^3 (an Fp4 factor the
    final exponentiation removes): (lam*x_T - y_T) - lam*x_P w^2 + y_P w^3."""
    (xt, yt), (xp, yp) = t, p
    if q is None:
        lam = f2_mul(f2_muls(f2_sqr(xt), 3), f2_inv(f2_muls(yt, 2)))
        x3 = f2_sub(f2_sqr(lam), f2_muls(xt, 2))
    else:
        xq, yq = q
        lam = f2_mul(f2_sub(yq, yt), f2_inv(f2_sub(xq, xt)))
        x3 = f2_sub(f2_sub(f2_sqr(lam), xt), xq)
    y3 = f2_sub(f2_mul(lam, f2_sub(xt, x3)), yt)
    c0 = f2_sub(f2_mul(lam, xt), yt)
    c1 = f2_neg(f2_muls(lam, xp))
    line = ((c0, c1, F2_ZERO), (F2_ZERO, (yp, 0), F2_ZERO))
    return (x3, y3), line


def miller_loop(p, q):
    """f_{|x|,Q}(P) for affine P in G1 and Q in G2 (the sign of x and the
    vertical lines only change the value by factors the final
    exponentiation removes or inverts, which a product test ignores)."""
    f, t = F12_ONE, q
    for bit in bin(-X)[3:]:
        t, line = _line(t, None, p)
        f = f12_mul(f12_sqr(f), line)
        if bit == "1":
            t, line = _line(t, q, p)
            f = f12_mul(f, line)
    return f


def _exp_x(a):
    """a^|x| (a in the cyclotomic subgroup)."""
    return f12_pow_u(a, -X)


def final_exponentiation_cubed(f):
    """f^(3(p^12-1)/r): the easy part f^((p^6-1)(p^2+1)), then the hard part
    times three as (x-1)^2 (x+p)(x^2+p^2-1) + 3. Powers by the negative x
    are conjugates of powers by |x| in the cyclotomic subgroup."""
    f = f12_mul(f12_conj(f), f12_inv(f))
    f = f12_mul(f12_frob(f12_frob(f)), f)

    def pow_x(a):
        return f12_conj(_exp_x(a))

    def pow_xm1(a):  # a^(x-1)
        return f12_mul(pow_x(a), f12_conj(a))

    a = pow_xm1(pow_xm1(f))
    b = f12_mul(pow_x(a), f12_frob(a))
    c = f12_mul(f12_mul(pow_x(pow_x(b)), f12_frob(f12_frob(b))), f12_conj(b))
    return f12_mul(c, f12_mul(f12_sqr(f), f))


assert ((X - 1) ** 2 * (X + P) * (X * X + P * P - 1) + 3
        == 3 * (P ** 4 - P ** 2 + 1) // R), "hard-part decomposition"


def pairing_product_is_one(pairs) -> bool:
    """prod e(P_i, Q_i) == 1 for affine (P_i, Q_i)."""
    f = F12_ONE
    for p, q in pairs:
        f = f12_mul(f, miller_loop(p, q))
    return final_exponentiation_cubed(f) == F12_ONE


def fast_aggregate_verify(pubkeys, msg: bytes, sig: bytes, pk_cache=None) -> bool:
    """FastAggregateVerify of the Ethereum BLS signature scheme: every key
    valid, e(sum of keys, H(msg)) == e(G1, sig). `pk_cache` maps a key's
    bytes to its validated point (a node keeps these for the period)."""
    if not pubkeys:
        return False
    cache = {} if pk_cache is None else pk_cache
    acc = None
    for pk in pubkeys:
        if pk not in cache:
            cache[pk] = key_validate(pk)
        a = cache[pk]
        if a is None:
            return False
        acc = pt_add(FP, acc, from_affine(FP, a))
    try:
        s = g2_decompress(sig)
    except ValueError:
        return False
    if s is None or not in_subgroup(FP2, s):
        return False
    agg = to_affine(FP, acc)
    if agg is None:
        return False
    neg_g1 = (G1[0], P - G1[1])
    return pairing_product_is_one([(agg, hash_to_g2(msg)), (neg_g1, s)])
