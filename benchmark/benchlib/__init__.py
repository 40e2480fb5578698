"""The benchmark's own library: everything here is the yardstick (traffic,
metric arithmetic, trace reduction, process handling). Nothing in this
package imports runbooks_tpu, and only tracefile.py imports JAX (CPU only,
in a child of its own)."""
