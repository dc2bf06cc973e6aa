"""The benchmark's own client of the gateway's msgpack framing: a 4-byte
big-endian length, then one msgpack map.  Imports nothing of the program
and nothing of JAX, so the processes that drive traffic stay off the
chip and off the server's interpreter lock."""

import socket
import struct

import msgpack

HEAD = struct.Struct('>I')


def frame(obj):
    """One request as wire bytes."""
    body = msgpack.packb(obj, use_bin_type=True)
    return HEAD.pack(len(body)) + body


def unpack(body):
    return msgpack.unpackb(body, raw=False, strict_map_key=False)


class Conn(object):
    """One blocking connection to the gateway's unix socket."""

    def __init__(self, path):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.connect(path)
        self._buf = bytearray()

    def send(self, data):
        self.sock.sendall(data)

    def read_body(self):
        """The next frame's msgpack body, or None at end of stream."""
        while True:
            if len(self._buf) >= 4:
                (n,) = HEAD.unpack_from(self._buf)
                if len(self._buf) >= 4 + n:
                    body = bytes(self._buf[4:4 + n])
                    del self._buf[:4 + n]
                    return body
            chunk = self.sock.recv(1 << 20)
            if not chunk:
                return None
            self._buf += chunk

    def call(self, obj):
        """One request, answered before the next is sent."""
        self.send(frame(obj))
        body = self.read_body()
        if body is None:
            raise ConnectionError('gateway closed the connection')
        return unpack(body)

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass
