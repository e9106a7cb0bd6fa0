"""vswitch tests — TestPacket (codec round-trips), TestRouteTable
(insert-order LPM), and in-process switch networks linked over loopback
UDP exercising ARP/NDP/ICMP/L2 learning and cross-VNI routing."""
import socket
import time

import pytest

from vproxy_tpu.components.elgroup import EventLoopGroup
from vproxy_tpu.rules.ir import RouteRule
from vproxy_tpu.utils.ip import Network, parse_ip
from vproxy_tpu.vswitch import packets as P
from vproxy_tpu.vswitch.network import VpcNetwork
from vproxy_tpu.vswitch.switch import Switch, synthetic_mac


# ----------------------------------------------------------------- codecs

def test_ethernet_arp_roundtrip():
    arp = P.Arp(P.ARP_REQUEST, sha=P.parse_mac("02:00:00:00:00:01"),
                spa=parse_ip("10.0.0.1"), tha=b"\x00" * 6,
                tpa=parse_ip("10.0.0.2"))
    e = P.Ethernet(P.BROADCAST_MAC, arp.sha, P.ETHER_TYPE_ARP, b"", arp)
    raw = e.to_bytes()
    e2 = P.Ethernet.parse(raw)
    assert isinstance(e2.packet, P.Arp)
    assert e2.packet.spa == arp.spa and e2.packet.op == P.ARP_REQUEST
    assert e2.to_bytes() == raw


def test_ipv4_icmp_roundtrip_checksums():
    icmp = P.Icmp(P.ICMP_ECHO_REQ, 0, b"\x12\x34\x00\x01payload")
    ip = P.Ipv4(parse_ip("10.0.0.1"), parse_ip("10.0.0.2"), P.PROTO_ICMP,
                b"", packet=icmp)
    raw = ip.to_bytes()
    # header checksum must validate
    assert P.checksum(raw[:20]) == 0
    ip2 = P.Ipv4.parse(raw)
    assert isinstance(ip2.packet, P.Icmp)
    assert ip2.packet.body == icmp.body
    # icmp checksum validates
    assert P.checksum(raw[20:]) == 0


def test_tcp_udp_roundtrip():
    tcp = P.Tcp(1234, 80, seq=1000, ack=0, flags=P.TCP_SYN, window=65535,
                options=b"\x02\x04\x05\xb4")
    ip = P.Ipv4(parse_ip("10.0.0.1"), parse_ip("10.0.0.2"), P.PROTO_TCP,
                b"", packet=tcp)
    ip2 = P.Ipv4.parse(ip.to_bytes())
    assert isinstance(ip2.packet, P.Tcp)
    assert ip2.packet.mss_option() == 1460
    assert ip2.packet.flags == P.TCP_SYN

    udp = P.Udp(53, 5353, b"hello")
    ip6 = P.Ipv6(parse_ip("fd00::1"), parse_ip("fd00::2"), P.PROTO_UDP,
                 b"", packet=udp)
    ip62 = P.Ipv6.parse(ip6.to_bytes())
    assert isinstance(ip62.packet, P.Udp) and ip62.packet.data == b"hello"


def test_vxlan_and_encrypted_roundtrip():
    pytest.importorskip("cryptography")  # encrypted frames use AES-CFB
    arp = P.Arp(P.ARP_REPLY, sha=b"\x02" * 6, spa=parse_ip("10.1.0.1"),
                tha=b"\x04" * 6, tpa=parse_ip("10.1.0.2"))
    e = P.Ethernet(b"\x04" * 6, b"\x02" * 6, P.ETHER_TYPE_ARP, b"", arp)
    vx = P.Vxlan(1314, e)
    vx2 = P.Vxlan.parse(vx.to_bytes())
    assert vx2.vni == 1314 and isinstance(vx2.ether.packet, P.Arp)

    import hashlib
    key = hashlib.sha256(b"pass123").digest()

    def key_for(user):
        return key if user == "alice5AA" else None

    sp = P.VProxySwitchPacket("alice5AA", P.VPROXY_TYPE_VXLAN, vx)
    raw = sp.to_bytes(key_for)
    sp2 = P.VProxySwitchPacket.parse(raw, key_for)
    assert sp2.user == "alice5AA" and sp2.vxlan.vni == 1314

    with pytest.raises(P.PacketError):
        P.VProxySwitchPacket.parse(raw, lambda u: hashlib.sha256(b"x").digest())


# ------------------------------------------------------------ route table

def test_route_table_insert_order_lpm():
    # TestRouteTable analog: most-specific-first among overlapping rules
    net = VpcNetwork(1, Network.parse("10.0.0.0/8"))
    net.add_route(RouteRule("wide", Network.parse("10.0.0.0/8"), to_vni=1))
    net.add_route(RouteRule("mid", Network.parse("10.1.0.0/16"), to_vni=2))
    net.add_route(RouteRule("narrow", Network.parse("10.1.2.0/24"), to_vni=3))
    assert net.route_lookup(parse_ip("10.1.2.3")).alias == "narrow"
    assert net.route_lookup(parse_ip("10.1.9.9")).alias == "mid"
    assert net.route_lookup(parse_ip("10.9.9.9")).alias == "wide"
    assert net.route_lookup(parse_ip("11.0.0.1")) is None
    net.remove_route("narrow")
    assert net.route_lookup(parse_ip("10.1.2.3")).alias == "mid"
    with pytest.raises(ValueError):
        net.add_route(RouteRule("mid", Network.parse("10.3.0.0/16"), to_vni=9))


# --------------------------------------------------------- switch end2end

class FakeHost:
    """A VXLAN VTEP host simulated with one UDP socket: sends/receives
    encapsulated frames for a (mac, ip) endpoint."""

    def __init__(self, mac: str, ip: str, vni: int, switch_addr):
        self.mac = P.parse_mac(mac)
        self.ip = parse_ip(ip)
        self.vni = vni
        self.switch_addr = switch_addr
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.settimeout(5)

    def send_ether(self, ether: P.Ethernet):
        self.sock.sendto(P.Vxlan(self.vni, ether).to_bytes(), self.switch_addr)

    def recv_ether(self, want=None, timeout=5.0):
        t0 = time.time()
        while time.time() - t0 < timeout:
            try:
                data, _ = self.sock.recvfrom(65536)
            except socket.timeout:
                break
            vx = P.Vxlan.parse(data)
            if want is None or want(vx.ether):
                return vx.ether
        raise TimeoutError("no matching frame")

    def gratuitous_arp(self):
        arp = P.Arp(P.ARP_REPLY, sha=self.mac, spa=self.ip, tha=self.mac,
                    tpa=self.ip)
        self.send_ether(P.Ethernet(P.BROADCAST_MAC, self.mac,
                                   P.ETHER_TYPE_ARP, b"", arp))

    def arp_request(self, target_ip: str):
        arp = P.Arp(P.ARP_REQUEST, sha=self.mac, spa=self.ip,
                    tha=b"\x00" * 6, tpa=parse_ip(target_ip))
        self.send_ether(P.Ethernet(P.BROADCAST_MAC, self.mac,
                                   P.ETHER_TYPE_ARP, b"", arp))

    def ping(self, dst_mac: bytes, dst_ip: str, ident=b"\x00\x07\x00\x01"):
        icmp = P.Icmp(P.ICMP_ECHO_REQ, 0, ident + b"ping-data")
        ip = P.Ipv4(self.ip, parse_ip(dst_ip), P.PROTO_ICMP, b"", packet=icmp)
        self.send_ether(P.Ethernet(dst_mac, self.mac, P.ETHER_TYPE_IPV4,
                                   b"", ip))

    def close(self):
        self.sock.close()


@pytest.fixture
def sw_env():
    elg = EventLoopGroup("sw", 1)
    objs = {"switches": [], "hosts": []}
    yield elg, objs
    for s in objs["switches"]:
        s.stop()
    for h in objs["hosts"]:
        h.close()
    time.sleep(0.05)
    elg.close()


def test_switch_arp_and_icmp_for_synthetic_ip(sw_env):
    elg, objs = sw_env
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0)
    objs["switches"].append(sw)
    sw.start()
    net = sw.add_network(1314, Network.parse("172.16.0.0/16"))
    gw_ip = parse_ip("172.16.0.1")
    net.ips.add(gw_ip, synthetic_mac(1314, gw_ip))

    h = FakeHost("02:aa:00:00:00:01", "172.16.0.11", 1314,
                 ("127.0.0.1", sw.bind_port))
    objs["hosts"].append(h)
    # ARP who-has 172.16.0.1 -> switch answers with the synthetic mac
    h.arp_request("172.16.0.1")
    reply = h.recv_ether(lambda e: isinstance(e.packet, P.Arp)
                         and e.packet.op == P.ARP_REPLY)
    assert reply.packet.sha == synthetic_mac(1314, gw_ip)
    assert reply.packet.spa == gw_ip
    # ICMP echo to the synthetic ip -> echo reply
    h.ping(reply.packet.sha, "172.16.0.1")
    echo = h.recv_ether(lambda e: isinstance(e.packet, P.Ipv4)
                        and isinstance(e.packet.packet, P.Icmp)
                        and e.packet.packet.type == P.ICMP_ECHO_REPLY)
    assert echo.packet.packet.body.endswith(b"ping-data")
    assert echo.packet.src == gw_ip


def test_switch_l2_forwarding_between_hosts(sw_env):
    elg, objs = sw_env
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0)
    objs["switches"].append(sw)
    sw.start()
    sw.add_network(2, Network.parse("10.2.0.0/16"))
    addr = ("127.0.0.1", sw.bind_port)
    h1 = FakeHost("02:aa:00:00:00:11", "10.2.0.11", 2, addr)
    h2 = FakeHost("02:aa:00:00:00:12", "10.2.0.12", 2, addr)
    objs["hosts"] += [h1, h2]
    h1.gratuitous_arp()  # switch learns h1's mac+iface
    h2.gratuitous_arp()
    time.sleep(0.1)
    # h1 -> h2 unicast ping is forwarded to h2's socket (known unicast)
    h1.ping(h2.mac, "10.2.0.12")
    got = h2.recv_ether(lambda e: isinstance(e.packet, P.Ipv4)
                        and isinstance(e.packet.packet, P.Icmp))
    assert got.packet.src == h1.ip and got.packet.dst == h2.ip
    assert got.src == h1.mac


def test_switch_cross_vni_routing(sw_env):
    elg, objs = sw_env
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0)
    objs["switches"].append(sw)
    sw.start()
    n1 = sw.add_network(101, Network.parse("10.1.0.0/16"))
    n2 = sw.add_network(102, Network.parse("10.2.0.0/16"))
    # synthetic gateways in both networks
    for net, gw in ((n1, "10.1.0.1"), (n2, "10.2.0.1")):
        ip = parse_ip(gw)
        net.ips.add(ip, synthetic_mac(net.vni, ip))
    n1.add_route(RouteRule("to2", Network.parse("10.2.0.0/16"), to_vni=102))
    addr = ("127.0.0.1", sw.bind_port)
    h1 = FakeHost("02:aa:00:00:01:01", "10.1.0.11", 101, addr)
    h2 = FakeHost("02:aa:00:00:02:02", "10.2.0.22", 102, addr)
    objs["hosts"] += [h1, h2]
    h1.gratuitous_arp()
    h2.gratuitous_arp()  # also fills n2's arp table for delivery
    time.sleep(0.1)
    gw1_mac = synthetic_mac(101, parse_ip("10.1.0.1"))
    # h1 pings h2 via its gateway mac; the switch routes into vni 102
    h1.ping(gw1_mac, "10.2.0.22")
    got = h2.recv_ether(lambda e: isinstance(e.packet, P.Ipv4)
                        and isinstance(e.packet.packet, P.Icmp))
    assert got.packet.src == h1.ip and got.packet.dst == h2.ip
    assert got.packet.ttl == 63  # decremented on routing


def test_burst_routing_and_acl_batch(sw_env):
    """A burst of datagrams takes the batched path (_input_batch:
    batched bare-ACL + one LPM dispatch per vpc) with per-packet
    results identical to the single path; a default-deny ACL drops the
    whole burst."""
    from vproxy_tpu.components.secgroup import SecurityGroup
    from vproxy_tpu.rules.ir import AclRule, Proto

    elg, objs = sw_env
    allow_lo = SecurityGroup("lo-only", default_allow=False)
    allow_lo.add_rule(AclRule("lo", Network.parse("127.0.0.0/8"),
                              Proto.UDP, 0, 65535, True))
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0,
                bare_vxlan_access=allow_lo)
    objs["switches"].append(sw)
    sw.start()
    n1 = sw.add_network(101, Network.parse("10.1.0.0/16"))
    n2 = sw.add_network(102, Network.parse("10.2.0.0/16"))
    for net, gw in ((n1, "10.1.0.1"), (n2, "10.2.0.1")):
        ip = parse_ip(gw)
        net.ips.add(ip, synthetic_mac(net.vni, ip))
    n1.add_route(RouteRule("to2", Network.parse("10.2.0.0/16"), to_vni=102))
    addr = ("127.0.0.1", sw.bind_port)
    h1 = FakeHost("02:aa:00:00:01:01", "10.1.0.11", 101, addr)
    h2 = FakeHost("02:aa:00:00:02:02", "10.2.0.22", 102, addr)
    objs["hosts"] += [h1, h2]
    h1.gratuitous_arp()
    h2.gratuitous_arp()
    time.sleep(0.1)
    gw1_mac = synthetic_mac(101, parse_ip("10.1.0.1"))
    n_burst = 100
    for i in range(n_burst):  # one tight burst: kernel queues them all
        h1.ping(gw1_mac, "10.2.0.22", ident=b"\x00\x07" + i.to_bytes(2, "big"))
    got = set()
    deadline = time.time() + 5
    while len(got) < n_burst and time.time() < deadline:
        e = h2.recv_ether(lambda e: isinstance(e.packet, P.Ipv4)
                          and isinstance(e.packet.packet, P.Icmp))
        assert e.packet.ttl == 63
        got.add(e.packet.packet.body[2:4])
    assert len(got) == n_burst

    # default-deny group: the same burst never comes out
    deny = SecurityGroup("deny-all", default_allow=False)
    sw2 = Switch("sw1", elg.next(), "127.0.0.1", 0, bare_vxlan_access=deny)
    objs["switches"].append(sw2)
    sw2.start()
    d1 = sw2.add_network(101, Network.parse("10.1.0.0/16"))
    d2 = sw2.add_network(102, Network.parse("10.2.0.0/16"))
    for net, gw in ((d1, "10.1.0.1"), (d2, "10.2.0.1")):
        ip = parse_ip(gw)
        net.ips.add(ip, synthetic_mac(net.vni, ip))
    d1.add_route(RouteRule("to2", Network.parse("10.2.0.0/16"), to_vni=102))
    addr2 = ("127.0.0.1", sw2.bind_port)
    g1 = FakeHost("02:aa:00:00:01:01", "10.1.0.11", 101, addr2)
    g2 = FakeHost("02:aa:00:00:02:02", "10.2.0.22", 102, addr2)
    objs["hosts"] += [g1, g2]
    g1.gratuitous_arp()
    g2.gratuitous_arp()
    for _ in range(10):
        g1.ping(gw1_mac, "10.2.0.22")
    with pytest.raises(TimeoutError):
        g2.recv_ether(lambda e: isinstance(e.packet, P.Ipv4), timeout=0.6)


def test_two_switches_linked(sw_env):
    elg, objs = sw_env
    sw1 = Switch("sw1", elg.next(), "127.0.0.1", 0)
    sw2 = Switch("sw2", elg.next(), "127.0.0.1", 0)
    objs["switches"] += [sw1, sw2]
    sw1.start()
    sw2.start()
    sw1.add_network(7, Network.parse("10.7.0.0/16"))
    sw2.add_network(7, Network.parse("10.7.0.0/16"))
    sw1.add_remote_switch("to2", "127.0.0.1", sw2.bind_port)
    sw2.add_remote_switch("to1", "127.0.0.1", sw1.bind_port)
    h1 = FakeHost("02:bb:00:00:00:01", "10.7.0.1", 7, ("127.0.0.1", sw1.bind_port))
    h2 = FakeHost("02:bb:00:00:00:02", "10.7.0.2", 7, ("127.0.0.1", sw2.bind_port))
    objs["hosts"] += [h1, h2]
    h1.gratuitous_arp()
    h2.gratuitous_arp()
    time.sleep(0.15)
    # broadcast ARP from h1 floods across the switch link to h2
    h1.arp_request("10.7.0.2")
    req = h2.recv_ether(lambda e: isinstance(e.packet, P.Arp)
                        and e.packet.op == P.ARP_REQUEST)
    assert req.packet.spa == h1.ip
    # h2 replies unicast; mac learning carries it back through the link
    arp = P.Arp(P.ARP_REPLY, sha=h2.mac, spa=h2.ip, tha=h1.mac, tpa=h1.ip)
    h2.send_ether(P.Ethernet(h1.mac, h2.mac, P.ETHER_TYPE_ARP, b"", arp))
    rep = h1.recv_ether(lambda e: isinstance(e.packet, P.Arp)
                        and e.packet.op == P.ARP_REPLY)
    assert rep.packet.sha == h2.mac
    # unicast ping h1 -> h2 through the link
    h1.ping(h2.mac, "10.7.0.2")
    got = h2.recv_ether(lambda e: isinstance(e.packet, P.Ipv4)
                        and isinstance(e.packet.packet, P.Icmp))
    assert got.packet.src == h1.ip


def test_encrypted_user_tunnel(sw_env):
    pytest.importorskip("cryptography")  # encrypted frames use AES-CFB
    elg, objs = sw_env
    # server switch with a configured user; client switch dials in
    server = Switch("server", elg.next(), "127.0.0.1", 0)
    client = Switch("client", elg.next(), "127.0.0.1", 0)
    objs["switches"] += [server, client]
    server.start()
    client.start()
    server.add_network(9, Network.parse("10.9.0.0/16"))
    client.add_network(9, Network.parse("10.9.0.0/16"))
    server.add_user("alice5AA", "sekrit", 9)
    client.add_user_client("alice5AA", "sekrit", 9, "127.0.0.1",
                           server.bind_port)
    time.sleep(0.2)  # ping keepalive registers the user iface server-side
    assert any(i.name == "user:alice5AA" for i in server.list_ifaces())
    # host on the server side and host on the client side exchange frames
    hs = FakeHost("02:cc:00:00:00:01", "10.9.0.1", 9,
                  ("127.0.0.1", server.bind_port))
    hc = FakeHost("02:cc:00:00:00:02", "10.9.0.2", 9,
                  ("127.0.0.1", client.bind_port))
    objs["hosts"] += [hs, hc]
    hs.gratuitous_arp()
    hc.gratuitous_arp()
    time.sleep(0.15)
    hs.arp_request("10.9.0.2")  # floods through the encrypted tunnel
    req = hc.recv_ether(lambda e: isinstance(e.packet, P.Arp)
                        and e.packet.op == P.ARP_REQUEST)
    assert req.packet.spa == hs.ip


def test_switch_command_grammar(sw_env):
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.control import persist
    app = Application.create(workers=1)
    try:
        Command.execute(app, "add switch sw0 address 127.0.0.1:0")
        Command.execute(app, "add vpc 1314 to switch sw0 v4network 172.16.0.0/16")
        Command.execute(app, "add ip 172.16.0.21 to vpc 1314 in switch sw0")
        Command.execute(app, "add route r1 to vpc 1314 in switch sw0 "
                             "network 172.17.0.0/16 vni 1315")
        Command.execute(app, "add user bob00000 to switch sw0 password pw vni 1314")
        assert Command.execute(app, "list vpc in switch sw0") == ["1314"]
        assert Command.execute(app, "list user in switch sw0") == ["bob00000"]
        routes = Command.execute(app, "list-detail route in vpc 1314 in switch sw0")
        assert routes == ["r1 -> network 172.17.0.0/16 vni 1315"]
        cfg = persist.current_config(app)
        assert "add switch sw0 address" in cfg
        assert "add vpc 1314 to switch sw0 v4network 172.16.0.0/16" in cfg
        assert "add user bob00000 to switch sw0 password pw vni 1314" in cfg
        Command.execute(app, "remove route r1 from vpc 1314 in switch sw0")
        assert Command.execute(app, "list route in vpc 1314 in switch sw0") == []
        Command.execute(app, "remove switch sw0")
        assert Command.execute(app, "list switch") == []
    finally:
        app.close()


# ------------------------------------------------- the route-table set

def _routed_vpcs(sw, sizes):
    """VPC 100+k with sizes[k] routes 10.<k>.<j>.0/24 -> vni 100, all of
    them also holding 10.0.0.0/8 (the same prefix in every tenant)."""
    nets = []
    for k, n in enumerate(sizes):
        net = sw.add_network(100 + k, Network.parse(f"10.{k}.0.0/16"))
        net.set_routes(
            [RouteRule(f"r{k}-{j}", Network.parse(f"10.{k}.{j}.0/24"),
                       to_vni=100) for j in range(n)]
            + [RouteRule(f"wide{k}", Network.parse("10.0.0.0/8"),
                         to_vni=100 + k)])
        nets.append(net)
    return nets


@pytest.mark.parametrize("backend,in_set", [("jax", True), ("host", True),
                                            ("jax-dense", False)])
def test_switch_vpcs_share_one_route_table_set(sw_env, backend, in_set):
    """A switch's VPCs are views of ONE set a family where the backend
    has a set, matchers of their own elsewhere; del_network gives the
    view back; the answers are the VPC's own either way."""
    elg, objs = sw_env
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0, matcher_backend=backend)
    objs["switches"].append(sw)
    n0, n1, n2 = _routed_vpcs(sw, [3, 5, 2])
    sets = sw.route_sets()
    assert (sets is not None) == in_set
    if in_set:
        assert n0._matcher_v4.table_set is n1._matcher_v4.table_set is sets[0]
        assert n0._matcher_v6.table_set is sets[1]
        assert sets[0].size() == 3 + 5 + 2 + 3 and sets[1].size() == 0
    ip = parse_ip("10.1.4.9")       # a /24 of VPC 101, the /8 elsewhere
    assert n1.route_lookup(ip).alias == "r1-4"
    assert n0.route_lookup(ip).alias == "wide0"
    assert n2.route_lookup(ip).alias == "wide2"
    got = n1.route_lookup_batch([ip, parse_ip("10.1.9.9"),
                                 parse_ip("11.0.0.1")])
    assert [r and r.alias for r in got] == ["r1-4", "wide1", None]
    sw.del_network(101)
    if in_set:
        assert sets[0].size() == 3 + 2 + 2
        assert n1._matcher_v4.size() == 0
    assert n0.route_lookup(ip).alias == "wide0"
    n3 = sw.add_network(103, Network.parse("10.3.0.0/16"))
    n3.add_route(RouteRule("only", Network.parse("10.1.4.0/24"), to_vni=100))
    assert n3.route_lookup(ip).alias == "only"
    assert n3.route_lookup(parse_ip("10.9.9.9")) is None
    n3.remove_route("only")
    assert n3.route_lookup(ip) is None


def test_route_flush_of_a_mixed_vpc_burst_is_one_dispatch(sw_env):
    """The burst's deferred lookups, three VPCs and both families mixed
    (the set past SMALL_TABLE, so the device serves): one launch for the
    v4 lookups of every VPC, each answered from its own VPC's table."""
    from vproxy_tpu.rules import engine
    elg, objs = sw_env
    sw = Switch("sw0", elg.next(), "127.0.0.1", 0, matcher_backend="jax")
    objs["switches"].append(sw)
    nets = _routed_vpcs(sw, [60, 90, 40])
    nets[2].add_route(RouteRule("six", Network.parse("fd00::/16"),
                                to_vni=100))
    assert sw.route_sets()[0].size() > engine.SMALL_TABLE
    pend, want = [], []
    for i in range(120):
        k = i % 3
        dst = f"10.{(i // 3) % 3}.{i % 50}.7"
        ip = P.Ipv4(parse_ip("10.9.9.9"), parse_ip(dst), P.PROTO_UDP, b"")
        pend.append((nets[k], None, ip, False))
        want.append(nets[k].routes.lookup(parse_ip(dst)).alias)
    six = P.Ipv6(parse_ip("fd00::1"), parse_ip("fd00::2"), P.PROTO_UDP, b"")
    pend.append((nets[2], None, six, True))
    want.append("six")
    pend.append((nets[0], None, six, True))     # VPC 100 routes no v6
    want.append(None)
    got = []
    sw.stack._route_with = lambda n, e, ip, v6, rule: got.append(
        rule and rule.alias)
    launches = engine.dispatch_launches_total()
    sw.stack._route_flush(pend)
    assert got == want
    assert {w[:2] for w in want if w} >= {"r0", "r1", "r2", "wi", "si"}
    # v4: one launch for all three VPCs; v6: the set is small, host scan
    assert engine.dispatch_launches_total() == launches + 1


def test_config_replay_syncs_a_vpcs_routes_once(tmp_path):
    """persist.load holds the matcher syncs of `add route` lines and
    makes one a VPC at the end of the replay."""
    from vproxy_tpu.control import persist
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.rules import engine
    app = Application(workers=1)
    try:
        cfg = tmp_path / "cfg"
        lines = ["add switch sw0 address 127.0.0.1:0",
                 "add vpc 7 to switch sw0 v4network 10.7.0.0/16"]
        lines += [f"add route r{j} to vpc 7 in switch sw0 network "
                  f"10.7.{j}.0/24 vni 7" for j in range(12)]
        cfg.write_text("\n".join(lines) + "\n")
        builds = engine.cidr_set_table_builds_total()
        assert persist.load(app, str(cfg)) == len(lines)
        assert app.held_route_syncs is None
        net = app.switches["sw0"].networks[7]
        assert engine.cidr_set_table_builds_total() == builds + 1
        assert net.route_lookup(parse_ip("10.7.5.1")).alias == "r5"
        # an operator's own command syncs at once
        Command.execute(app, "add route late to vpc 7 in switch sw0 "
                             "network 10.8.0.0/16 vni 7")
        assert net.route_lookup(parse_ip("10.8.0.1")).alias == "late"
    finally:
        for sw in app.switches.values():
            sw.stop()
        for elg in app.elgs.values():
            elg.close()
