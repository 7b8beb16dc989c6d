SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '1996-01-01'
  AND l_shipdate < DATE '1996-01-01' + INTERVAL '1' YEAR
  AND l_discount BETWEEN 0.02 AND 0.04
  AND l_quantity < 25
