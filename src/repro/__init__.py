"""Paper reproduction: on-package memory over UCIe (approaches A-E),
roofline analysis of compiled workloads, and the workload->design-space
bridge connecting them."""
