"""Driver ``serve_open_loop``: requests arrive on their own clock
(``harness/traffic.py``) whether or not the fleet keeps up, as independent
users send them."""
from bench.harness import serving, traffic


def run(sess, control=False):
    arrivals = traffic.arrivals(sess.traffic, sess.seed, sess.seconds,
                                sess.config["vocab_size"])
    return serving.serve(sess, arrivals, control=control)
