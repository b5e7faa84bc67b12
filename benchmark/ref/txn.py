"""Plain Solana legacy transaction wire format: build and parse.

Independent of the program's parser.  Wire layout: compact-u16 signature
count, the signatures, then the message: a 3-byte header (required
signatures, read-only signed, read-only unsigned), compact-u16 account
count and the 32-byte addresses, the 32-byte recent blockhash, and
compact-u16 instruction count with each instruction as program index,
compact-u16 account-index count and indexes, compact-u16 data length and
data.  The first `required signatures` addresses are the signers, in
signature order.
"""

MTU = 1232


def cu16(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def read_cu16(b: bytes, i: int) -> tuple[int, int]:
    v = shift = 0
    for k in range(3):
        c = b[i + k]
        v |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            if k and c == 0:
                raise ValueError("non-minimal compact-u16")
            return v, i + k + 1
    raise ValueError("compact-u16 longer than 3 bytes")


def message(signers: list[bytes], others: list[bytes], programs: list[bytes],
            blockhash: bytes, instrs: list[tuple[int, bytes, bytes]],
            ro_signed: int = 0) -> bytes:
    """Legacy message; instrs are (program index, account indexes, data).
    Programs are the read-only unsigned accounts, placed last."""
    keys = signers + others + programs
    out = bytearray((len(signers), ro_signed, len(programs)))
    out += cu16(len(keys))
    for k in keys:
        out += k
    out += blockhash
    out += cu16(len(instrs))
    for prog, accts, data in instrs:
        out.append(prog)
        out += cu16(len(accts)) + accts
        out += cu16(len(data)) + data
    return bytes(out)


def assemble(sigs: list[bytes], msg: bytes) -> bytes:
    return cu16(len(sigs)) + b"".join(sigs) + msg


def parse(wire: bytes) -> tuple[list[bytes], list[bytes], bytes]:
    """(signatures, signer public keys, message) of a legacy transaction;
    raises ValueError on a malformed one."""
    if len(wire) > MTU:
        raise ValueError("larger than the packet")
    n, i = read_cu16(wire, 0)
    if not 1 <= n <= 127 or i + 64 * n > len(wire):
        raise ValueError("bad signature count")
    sigs = [wire[i + 64 * k:i + 64 * (k + 1)] for k in range(n)]
    moff = i + 64 * n
    if wire[moff] & 0x80 or wire[moff] != n:
        raise ValueError("not a legacy message signed by its signers")
    nkeys, j = read_cu16(wire, moff + 3)
    if nkeys < n or j + 32 * nkeys + 32 > len(wire):
        raise ValueError("bad account count")
    keys = [wire[j + 32 * k:j + 32 * (k + 1)] for k in range(nkeys)]
    j += 32 * nkeys + 32
    ninstr, j = read_cu16(wire, j)
    for _ in range(ninstr):
        if wire[j] >= nkeys:
            raise ValueError("program index out of range")
        na, j = read_cu16(wire, j + 1)
        if any(x >= nkeys for x in wire[j:j + na]):
            raise ValueError("account index out of range")
        nd, j = read_cu16(wire, j + na)
        j += nd
    if j != len(wire):
        raise ValueError("trailing or missing bytes")
    return sigs, keys[:n], wire[moff:]
