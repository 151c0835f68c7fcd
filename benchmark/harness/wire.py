"""Blocking MySQL protocol-41 client: login + COM_QUERY text results.

Plain sockets, no driver and nothing of the program: the load generator's
child process imports this and never JAX. Copied from `chip_smoke.py`'s
`WireClient` (sound; PR 22).
"""

from __future__ import annotations

import socket
import struct


class WireError(RuntimeError):
    pass


class WireClient:
    def __init__(self, port: int, timeout: float = 600.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._read()  # greeting
        caps = 0x0200 | 0x8000  # PROTOCOL_41 | SECURE_CONNECTION
        self._send(struct.pack("<IIB23x", caps, 1 << 24, 33)
                   + b"root\x00" + b"\x00", seq=1)
        if self._read()[0] != 0x00:
            raise WireError("login refused")

    def close(self) -> None:
        self.sock.close()

    def _read_n(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise WireError("peer closed the connection")
            buf += c
        return bytes(buf)

    def _read(self) -> bytes:
        head = self._read_n(4)
        return self._read_n(int.from_bytes(head[:3], "little"))

    def _send(self, payload: bytes, seq: int = 0) -> None:
        self.sock.sendall(
            len(payload).to_bytes(3, "little") + bytes([seq]) + payload)

    @staticmethod
    def _lenenc(buf: bytes, pos: int) -> tuple[int, int]:
        f = buf[pos]
        if f < 251:
            return f, pos + 1
        width = {0xFC: 2, 0xFD: 3, 0xFE: 8}[f]
        return (int.from_bytes(buf[pos + 1:pos + 1 + width], "little"),
                pos + 1 + width)

    def query(self, sql: str):
        """Rows (tuples of str | None) for a result set, the affected-row
        count for an OK packet; an ERR packet raises WireError."""
        self._send(b"\x03" + sql.encode())
        first = self._read()
        if first[0] == 0xFF:
            code = int.from_bytes(first[1:3], "little")
            raise WireError(
                f"ERR {code}: {first[9:].decode(errors='replace')} "
                f"<- {sql[:120]!r}")
        if first[0] == 0x00:
            return self._lenenc(first, 1)[0]
        ncols = self._lenenc(first, 0)[0]
        for _ in range(ncols):
            self._read()  # column definitions
        self._read()  # EOF
        rows = []
        while True:
            pkt = self._read()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows
            pos, row = 0, []
            for _ in range(ncols):
                if pkt[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    ln, pos = self._lenenc(pkt, pos)
                    row.append(pkt[pos:pos + ln].decode())
                    pos += ln
            rows.append(tuple(row))
